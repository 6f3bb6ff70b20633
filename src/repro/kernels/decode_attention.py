"""Pallas TPU paged decode attention (single-token q, GQA, online softmax
over KV pages).

The serve engine (repro.serve) stores the KV cache as PAGES: rows of one
flat f32 pool ``(n_pages, page_elems)``. A page holds one layer's K (or
V) for ``page_size`` tokens, head-major: ``n_kv`` slabs of ``(rows,
head_dim)``, with ``rows >= page_size`` (the pool geometry may pad).
``page_view`` is that view of the pool, ``(n_pages, n_kv, rows,
head_dim)``, a free reshape. Per-slot page tables map block j of request
b to pool rows ``rows_k[b, j]`` / ``rows_v[b, j]``. This kernel computes
one decode step's attention for the whole batch directly against those
pages.

Schedule: grid ``(B, n_kv, nblk)`` with the page index innermost
(sequential on TPU), VMEM scratch (m, l, acc) carrying the online softmax
across pages — the decode-shaped sibling of ``flash_attention`` (same
scratch dance, q-block = one token). The page tables and lengths ride
``PrefetchScalarGridSpec`` scalar prefetch, so the BlockSpec index_map
DMAs exactly the slab each grid step owns: block j of KV head h of batch
b streams ``page_view(pool)[rows_k[b, j], h]`` into VMEM — gathers never
materialize. Each cell is one KV head's ``(g, hd) x (page, hd)^T``
scores and ``(g, page) x (page, hd)`` values for its ``g`` query heads:
every block's last two dims are whole array dims (the TPU's (8, 128)
block rule), and nothing is reshaped in VMEM.

Bit-identity contract: ``paged_decode_attention`` in interpret mode and
``paged_decode_attention_ref`` agree BIT-FOR-BIT (the parity tests assert
exact equality), which takes three deliberate choices shared via
``_cell_update``:

1. Every float sum (scores, p@v, sum(p)) goes through
   ``lax.dot_general`` — a library call XLA cannot re-associate. A plain
   ``jnp.sum`` is re-tiled per fusion context: the same reduction
   compiled inside the pallas grid body vs. inside a ``lax.scan`` body
   rounds differently (~1 ulp, data-dependent), and
   ``optimization_barrier`` does not stop it.
2. The online-softmax accumulates ``l*corr + sum(p)`` and
   ``acc*corr + pv`` add through ``_pair_add`` (stack the two addends,
   contract with ones(2)) so neither program can FMA-contract the
   multiply into the add. The compiled TPU kernel has no bit-identity
   contract and adds plainly.
3. The reference runs per (batch row, KV head) (``lax.map``) with exactly the
   kernel's cell shapes, and mirrors the kernel's past-length block skip
   with a ``where`` on the scan carry — processing a fully-masked block
   is NOT bit-transparent, so the ref must skip precisely the blocks the
   kernel's ``pl.when`` skips.

The contract is validated in interpret mode (the only mode this
container can run); on real TPU hardware the compiled kernel's rounding
is hardware-specific and only the allclose tests apply.

Inactive slots are routed to the reserved trash page (row 0) with
length 1 by the engine: they compute finite garbage that never crosses
slots (every op here is batch-elementwise over b).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import named_pallas_call

NEG_INF = -1e30


def _pair_add(a, b, exact: bool):
    """``a + b``; with ``exact`` the add goes through dot_general so it
    cannot be FMA-contracted with whatever produced ``a`` or ``b`` (the
    interpret-mode bit-identity contract). The compiled TPU kernel has no
    such contract and adds plainly."""
    if not exact:
        return a + b
    t = jnp.stack([a, b], axis=-1)
    return jax.lax.dot_general(
        t, jnp.ones((2,), jnp.float32), (((t.ndim - 1,), (0,)), ((), ())))


def _dot(a, b, contract: int, exact: bool):
    """2-D ``a @ b`` contracting a's dim 1 with b's dim ``contract``. With
    ``exact`` the product carries a unit batch dim: XLA rewrites an
    unbatched product with a unit row count (one query head per KV head)
    into a reduction that it tiles per fusion context, which would round
    differently in the kernel body and in the reference's scan."""
    dims = (((1,), (contract,)), ((), ()))
    if not exact:
        return jax.lax.dot_general(a, b, dims)
    return jax.lax.dot_general(
        a[None], b[None], (((2,), (contract + 1,)), ((0,), (0,))))[0]


def _cell_update(q, k, v, cols, length, m_prev, l_prev, acc, scale,
                 exact: bool = True):
    """One page of online softmax for one KV head, on kernel-cell shapes:
    q (g, hd) f32 queries of that head's group, k/v (page, hd) token rows,
    cols (1, page) absolute positions, length scalar; carries m/l (g, 1)
    and acc (g, hd). Returns updated (m, l, acc). Shared verbatim by the
    kernel body and the reference — see the module docstring for why
    every reduction is a dot_general."""
    s = _dot(q, k, 1, exact) * scale               # (g, page)
    s = jnp.where(cols < length, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    pv = _dot(p, v, 0, exact)                      # (g, hd)
    psum = _dot(p, jnp.ones((p.shape[-1], 1), jnp.float32), 0,
                exact)                             # (g, 1)
    l_new = _pair_add(l_prev * corr, psum, exact)
    acc_new = _pair_add(acc * corr, pv, exact)
    return m_new, l_new, acc_new


def page_view(pool, n_kv: int, head_dim: int):
    """(n_pages, page_elems) pool -> (n_pages, n_kv, rows, head_dim): each
    page as its KV heads' token slabs (a free reshape). The paging
    geometry keeps page_elems a multiple of n_kv * head_dim
    (``paging.make_geom``)."""
    n_pages, pe = pool.shape
    assert pe % (n_kv * head_dim) == 0, (pe, n_kv, head_dim)
    return pool.reshape(n_pages, n_kv, pe // (n_kv * head_dim), head_dim)


def _kernel(rk_ref, rv_ref, len_ref, q_ref, kp_ref, vp_ref, o_ref,
            m_scr, l_scr, acc_scr, *, page_size, nblk, scale, exact):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages fully past the request's length are skipped; block 0 is
    # always valid (length >= 1), so m stays finite
    @pl.when(j * page_size < len_ref[b])
    def _work():
        cols = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        m, l, acc = _cell_update(
            q_ref[...], kp_ref[:page_size, :], vp_ref[:page_size, :], cols,
            len_ref[b], m_scr[...], l_scr[...], acc_scr[...], scale, exact)
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(j == nblk - 1)
    def _finish():
        o_ref[...] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def paged_decode_attention(q, pool, rows_k, rows_v, lengths, *,
                           page_size: int, n_kv: int,
                           interpret: bool = True):
    """q (B, H, hd); pool (n_pages, page_elems) f32 of head-major pages
    (``page_view``); rows_k/rows_v (B, nblk) int32 pool-row tables;
    lengths (B,) int32 (>= 1). Returns (B, H, hd) in q.dtype."""
    B, H, hd = q.shape
    assert H % n_kv == 0, (H, n_kv)
    g = H // n_kv
    nblk = rows_k.shape[1]
    pages = page_view(pool, n_kv, hd)
    rows = pages.shape[2]
    assert rows >= page_size, (pool.shape, page_size, n_kv, hd)
    kernel = functools.partial(
        _kernel, page_size=page_size, nblk=nblk,
        scale=1.0 / math.sqrt(hd), exact=interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_kv, nblk),
        in_specs=[
            pl.BlockSpec((None, None, g, hd),
                         lambda b, h, j, rk, rv, ln: (b, h, 0, 0)),
            pl.BlockSpec((None, None, rows, hd),
                         lambda b, h, j, rk, rv, ln: (rk[b, j], h, 0, 0)),
            pl.BlockSpec((None, None, rows, hd),
                         lambda b, h, j, rk, rv, ln: (rv[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, g, hd),
                               lambda b, h, j, rk, rv, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = named_pallas_call(
        "decode_attention",
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, g, hd), jnp.float32),
        interpret=interpret,
    )(rows_k, rows_v, lengths,
      q.astype(jnp.float32).reshape(B, n_kv, g, hd), pages, pages)
    return out.reshape(B, H, hd).astype(q.dtype)


def paged_decode_attention_ref(q, pool, rows_k, rows_v, lengths, *,
                               page_size: int, n_kv: int):
    """Pure-jnp reference, bit-identical to the interpret-mode kernel
    (same `_cell_update`, per-(row, KV head) lax.map so cell shapes match,
    skipped blocks masked on the carry — see module docstring). Also the
    impl='jnp' serve path."""
    B, H, hd = q.shape
    g = H // n_kv
    nblk = rows_k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    pages = page_view(pool, n_kv, hd)

    def one(args):
        qh, h, rk, rv, ln = args

        def step(carry, j):
            m_prev, l_prev, acc = carry
            k = pages[rk[j], h, :page_size]
            v = pages[rv[j], h, :page_size]
            cols = j * page_size + jnp.arange(
                page_size, dtype=jnp.int32)[None, :]
            m, l, a = _cell_update(qh, k, v, cols, ln, m_prev, l_prev,
                                   acc, scale)
            valid = j * page_size < ln
            return (jnp.where(valid, m, m_prev),
                    jnp.where(valid, l, l_prev),
                    jnp.where(valid, a, acc)), None

        (m, l, acc), _ = jax.lax.scan(
            step,
            (jnp.full((g, 1), NEG_INF, jnp.float32),
             jnp.zeros((g, 1), jnp.float32),
             jnp.zeros((g, hd), jnp.float32)),
            jnp.arange(nblk, dtype=jnp.int32))
        return acc / jnp.maximum(l, 1e-30)

    # one (batch row, KV head) cell per map step, b-major like the grid
    per = lambda x: jnp.repeat(x, n_kv, axis=0)
    out = jax.lax.map(one, (
        q.astype(jnp.float32).reshape(B * n_kv, g, hd),
        jnp.tile(jnp.arange(n_kv, dtype=jnp.int32), B),
        per(rows_k), per(rows_v), per(lengths)))
    return out.reshape(B, H, hd).astype(q.dtype)
