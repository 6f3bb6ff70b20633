"""Pallas TPU flash attention (causal, online softmax).

Grid (B*H, nq, nk) with the KV block index innermost (sequential on TPU), so
VMEM scratch (m, l, acc) persists across kv steps of the same q block — the
canonical TPU flash schedule. BlockSpec tiles q/k/v into (block, head_dim)
VMEM blocks; the causal structure is exploited with ``pl.when`` (blocks
strictly above the diagonal do no work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import named_pallas_call

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, block_q, block_k, scale, nk):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip kv blocks fully above the diagonal (block sizes may differ)
    @pl.when(kj * block_k < (qi + 1) * block_q)
    def _work():
        q = q_ref[0].astype(jnp.float32)       # (bq, hd)
        k = k_ref[0].astype(jnp.float32)       # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = (q @ k.T) * scale                  # (bq, bk)

        # intra-diagonal-block causal mask
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + p @ v
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    interpret: bool = True):
    """q,k,v (B,H,S,hd) -> (B,H,S,hd), causal. H == KV heads (pre-repeated).

    interpret=True runs the kernel body on CPU (this container); on TPU pass
    interpret=False for the compiled VMEM-tiled kernel.
    """
    B, H, S, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    bh = B * H
    qr = q.reshape(bh, S, hd)
    kr = k.reshape(bh, S, hd)
    vr = v.reshape(bh, S, hd)

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k,
        scale=1.0 / (hd ** 0.5), nk=nk)

    out = named_pallas_call(
        "flash_attention",
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd)
