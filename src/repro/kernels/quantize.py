"""Int8 per-chunk quantize/dequantize Pallas kernels (comm codecs).

The communication subsystem (repro.comm, DESIGN.md §8) compresses the
packed (G, N) model buffer before exchange. The int8 codec quantizes each
``chunk``-element slice with its own fp32 scale ``max|x| / 127`` and
unbiased stochastic rounding; the wire payload is 1 byte/element plus one
scale per chunk (~3.9x under fp32 at chunk=256).

Layout contract: callers reshape the flat buffer to ``(rows, chunk)``
(``optim.packing.chunk_rows``) — one row per chunk; each grid step takes
a block of ``row_block(rows)`` rows, so the per-row scale reduction, the
rounding, and the cast are a single VMEM pass per block.
Stochastic-rounding noise ``u`` (uniform [0,1)) is generated OUTSIDE with
``jax.random`` and passed in: the kernel stays deterministic given its
inputs, and the jnp reference path (codecs.py) consumes the same bits so
the two impls agree exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import named_pallas_call


# chunk rows per grid step: a multiple of 32 (the int8 sublane tile, and
# so of the (8, 128) block rule) and 512 KiB of f32 per operand block
ROW_BLOCK = 512


def row_block(rows: int) -> int:
    """Row-block height for a (rows, chunk) codec array: ROW_BLOCK, or
    all of ``rows`` when there are fewer (a block dim equal to the array
    dim is always legal on TPU). A partial last block is safe: every row
    is independent, and the rows past the array are never written."""
    return min(rows, ROW_BLOCK)


def _quantize_kernel(x_ref, u_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    # unbiased stochastic rounding: E[floor(v + u)] = v for u ~ U[0,1)
    q = jnp.floor(x / scale + u_ref[...].astype(jnp.float32))
    q_ref[...] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    s_ref[...] = scale


def quantize_int8(x, u, *, interpret: bool = True):
    """(rows, chunk) f32 + uniform noise -> (q int8 (rows, chunk),
    scales f32 (rows, 1)); one scale per row."""
    rows, chunk = x.shape
    rb = row_block(rows)
    return named_pallas_call(
        "quantize_int8",
        _quantize_kernel,
        grid=(pl.cdiv(rows, rb),),
        in_specs=[
            pl.BlockSpec((rb, chunk), lambda i: (i, 0)),
            pl.BlockSpec((rb, chunk), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((rb, chunk), lambda i: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows, chunk), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        interpret=interpret,
    )(x, u)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def dequantize_int8(q, scales, *, interpret: bool = True):
    """(rows, chunk) int8 + (rows, 1) scales -> (rows, chunk) f32."""
    rows, chunk = q.shape
    rb = row_block(rows)
    return named_pallas_call(
        "dequantize_int8",
        _dequantize_kernel,
        grid=(pl.cdiv(rows, rb),),
        in_specs=[
            pl.BlockSpec((rb, chunk), lambda i: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rb, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), jnp.float32),
        interpret=interpret,
    )(q, scales)
