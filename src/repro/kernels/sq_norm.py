"""Fused squared-L2-norm reduction Pallas kernel.

``grad_sq_norm`` is evaluated every local step (it drives the paper's
threshold mode and the Sec-4 adaptive-T controller). On a pytree that
materializes one partial sum per leaf; on the packed flat buffer it is a
single blocked reduction — the accumulator lives in a (G, 1) output block
and the sequential TPU grid accumulates into it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import named_pallas_call


def sq_norm(x, *, block: int = 65536, interpret: bool = True) -> jax.Array:
    """Sum of squares of a flat 1-D array -> f32 scalar."""
    return sq_norm_groups(x[None], block=block, interpret=interpret)[0]


def _kernel_groups(x_ref, o_ref, *, n: int, block: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    if n % block:
        # the last block runs past n: its tail columns hold whatever the
        # block buffer had, so zero them before they reach the sum
        col = j * block + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col < n, x, 0.0)
    o_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)


def sq_norm_groups(x, *, block: int = 65536,
                   interpret: bool = True) -> jax.Array:
    """Per-group sum of squares of a (G, N) array -> (G,) f32.

    One sequential grid over column blocks of all G rows at once: the
    input block is (G, block) and the (G, 1) accumulator is the whole
    output, so both satisfy the TPU's (8, 128) block rule (a block dim
    equal to the array dim is always legal) for any G. ``block`` is a
    multiple of 128 or all of N; a partial last block is masked in the
    kernel instead of padding (a pad would copy the whole buffer)."""
    g, n = x.shape
    block = n if n <= block else block
    kernel = functools.partial(_kernel_groups, n=n, block=block)
    out = named_pallas_call(
        "sq_norm",
        kernel,
        grid=(pl.cdiv(n, block),),
        in_specs=[pl.BlockSpec((g, block), lambda j: (0, j))],
        out_specs=pl.BlockSpec((g, 1), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 1), jnp.float32),
        interpret=interpret,
    )(x)
    return out[:, 0]
