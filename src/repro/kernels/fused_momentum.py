"""Fused heavy-ball momentum update Pallas kernel.

One VMEM pass over the packed flat buffer (optim.packing) per local step:
mu <- beta*mu + g; p <- p - lr*mu, with both outputs written from the same
block read — instead of one HLO fusion chain per pytree leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import as_rows, flat_blocks, named_pallas_call


def _kernel(p_ref, g_ref, mu_ref, po_ref, muo_ref, *, lr, beta):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    mu_new = beta * mu + g
    po_ref[...] = (p - lr * mu_new).astype(po_ref.dtype)
    muo_ref[...] = mu_new


def fused_momentum(p, g, mu, *, lr, beta=0.9, block: int = 65536,
                   interpret: bool = True):
    """Packed buffers p, g, mu of shape (N,) or (G, N). Returns (new_p,
    new_mu)."""
    p2 = as_rows(p)
    bs, grid = flat_blocks(p2.shape, block)
    spec = pl.BlockSpec(bs, lambda i: (0, i))
    new_p, new_mu = named_pallas_call(
        "fused_momentum",
        functools.partial(_kernel, lr=lr, beta=beta),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, p.dtype),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
        ],
        interpret=interpret,
    )(p2, as_rows(g), as_rows(mu))
    return new_p.reshape(p.shape), new_mu.reshape(p.shape)
