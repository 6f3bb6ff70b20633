"""Fused RMSNorm Pallas kernel: one pass over rows in VMEM blocks."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import named_pallas_call


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                 # (rows, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm(x, w, eps: float = 1e-5, block_rows: int = 128,
            interpret: bool = True):
    """x (..., D), w (D,). Rows are tiled into VMEM blocks of block_rows."""
    shape = x.shape
    D = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    xf = x.reshape(rows, D)
    block_rows = min(block_rows, rows)
    # pad rows to a block multiple
    pad = (-rows) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    n = xf.shape[0] // block_rows

    out = named_pallas_call(
        "rmsnorm",
        functools.partial(_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, w)
    if pad:
        out = out[:rows]
    return out.reshape(shape)
