"""Fused SGD update Pallas kernel.

The paper's local GD inner loop (T steps per communication) is the hot
path; on the packed flat buffer (optim.packing) the whole parameter update
is one VMEM pass: read p and g, write p - lr*g.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import as_rows, flat_blocks, named_pallas_call


def _kernel(p_ref, g_ref, po_ref, *, lr):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    po_ref[...] = (p - lr * g).astype(po_ref.dtype)


def fused_sgd(p, g, *, lr, block: int = 65536, interpret: bool = True):
    """Packed buffers p, g of shape (N,) or (G, N). Returns new_p."""
    p2 = as_rows(p)
    bs, grid = flat_blocks(p2.shape, block)
    spec = pl.BlockSpec(bs, lambda i: (0, i))
    new_p = named_pallas_call(
        "fused_sgd",
        functools.partial(_kernel, lr=lr),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(p2.shape, p.dtype),
        interpret=interpret,
    )(p2, as_rows(g))
    return new_p.reshape(p.shape)
