"""Fused codec-mix exchange epilogue kernels (DESIGN.md §11).

Once T is large the exchange phase IS the hot path (the paper prices
communication rounds as the scarce resource), yet the staged lossy
exchange makes 3-4 separate full-buffer passes per round: encode the
delta, decode it, mix over G, and (error-feedback codecs) update the
residual. These kernels collapse that chain into ONE pass over the flat
(G, N) buffer:

  ``codec_mix``     the whole replicated epilogue — encode + decode +
                    mean/W-row mixing (+ per-hop recompression for
                    ring/gossip, + EF residual update for the threshold
                    codec) — one Pallas grid over chunk-aligned column
                    blocks, every hop's work done while the block is in
                    VMEM.
  ``qdq_int8``      fused quantize+dequantize on (rows, chunk) — the
                    shard_map exchange's per-shard codec step (the mixing
                    there is a real collective between devices, so only
                    the codec fuses; previously two pallas_calls).

Kinds: ``int8`` (per-chunk scale + stochastic rounding, noise passed in
— same contract as kernels/quantize.py), ``bf16``/``fp16`` (cast),
``thresh`` (threshold selection with an error-feedback residual — the
element-wise part of top-k once the per-group threshold is known;
mean-mixing only).

Numerics contract: ``codec_mix(..., impl="jnp")`` is the STAGED
reference arranged as one function — the exact op sequence of
``comm.Exchange``'s staged path — and the Pallas kernel is bit-identical
to it (tests/test_exchange_engine.py): the per-block math is the same
jnp ops on the same shapes, the G-mean and the (G,G)x(G,B) W contraction
reduce in the same order per element.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import named_pallas_call
from repro.kernels.quantize import row_block

KINDS = ("int8", "bf16", "fp16", "thresh")

# column block of the codec_mix grid: a multiple of every codec chunk in
# use keeps per-chunk scales block-local (the int8 chunk is 256)
BLOCK_COLS = 2048


def _encode_decode(kind: str, d, u, chunk: int):
    """The codec's quantize+dequantize on a (G, B) delta block — the same
    element-wise math as the staged codecs (comm/codecs.py), so slicing
    columns before or after commutes bit-for-bit."""
    if kind in ("bf16", "fp16"):
        dt = jnp.bfloat16 if kind == "bf16" else jnp.float16
        return d.astype(dt).astype(d.dtype)
    assert kind == "int8", kind
    g = d.shape[0]
    rows = d.reshape(g, -1, chunk)
    amax = jnp.max(jnp.abs(rows), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.floor(rows / scale + u.reshape(rows.shape)),
                 -127.0, 127.0).astype(jnp.int8)
    return (q.astype(jnp.float32) * scale).reshape(d.shape)


def _mix_block(y, w):
    """One mixing application on a (G, B) block: exact mean+broadcast
    (w None — the server ops, bit-exact with ``average_groups``) or the
    W-row contraction (ring/gossip)."""
    if w is None:
        m = jnp.mean(y, axis=0, keepdims=True)
        return jnp.broadcast_to(m, y.shape)
    return jnp.tensordot(w, y, axes=[[1], [0]])


def _epilogue_block(kind, hops, chunk, x, x0, u, w, res, tau):
    """The whole fused epilogue on a (G, B) column block. Returns
    (mixed, residual_out) — residual_out is None except for ``thresh``."""
    if kind == "thresh":
        c = (x - x0) + res
        keep = (jnp.abs(c) >= tau) & (jnp.abs(c) > 0.0)
        d_hat = jnp.where(keep, c, 0.0)
        return _mix_block(x0 + d_hat, w), c - d_hat
    y, ref = x, x0
    for h in range(hops):
        d_hat = _encode_decode(kind, y - ref,
                               None if u is None else u[h], chunk)
        ref = ref + d_hat
        y = _mix_block(ref, w)
        if w is None:
            break  # mean mode: one compress + one exact mean
        # ring/gossip recompress per hop vs the transmitted payload (§8)
    return y, None


def codec_mix_ref(x, x0, *, kind: str, u=None, w=None, hops: int = 1,
                  chunk: int = 0, residual=None, tau=None
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Staged-op reference of the fused epilogue on the full (G, N)
    buffer. ``u``: (hops, G, N/chunk, chunk) stochastic-rounding noise
    (int8); ``tau``: (G, 1) per-group selection threshold (thresh);
    ``residual``: (G, N) error-feedback carry (thresh)."""
    assert kind in KINDS, kind
    w = None if w is None else jnp.asarray(w, jnp.float32)
    return _epilogue_block(kind, hops, chunk, x, x0, u, w, residual, tau)


def codec_mix(x, x0, *, kind: str, u=None, w=None, hops: int = 1,
              chunk: int = 0, residual=None, tau=None,
              impl: str = "jnp", interpret: bool = True,
              block_cols: int = BLOCK_COLS
              ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Fused codec+mix epilogue over a (G, N) flat buffer.

    impl="jnp" runs the staged reference in one XLA fusion; "pallas"
    runs the single-pass kernel (bit-identical — same block math). The
    column axis is zero-padded to a block multiple; zero columns are a
    fixed point of every kind (zero chunks quantize to zero, thresh
    never selects |c| = 0), so the pad never leaks and outputs slice
    back to N.
    """
    assert kind in KINDS, kind
    if kind == "thresh":
        assert w is None, "thresh fuses mean mixing only (DESIGN.md §11)"
        assert residual is not None and tau is not None
    if kind == "int8":
        assert u is not None and chunk > 0
    if impl == "jnp":
        # mirror chunk_rows: zero-pad the column axis to a chunk multiple
        # (the staged codec sees the same tail zeros — bit-identical)
        n = x.shape[-1]
        cpad = (-n) % chunk if chunk else 0
        if cpad:
            def pc(a):
                return jnp.pad(a, ((0, 0), (0, cpad)))

            x, x0 = pc(x), pc(x0)
            residual = None if residual is None else pc(residual)
        mixed, res_out = codec_mix_ref(x, x0, kind=kind, u=u, w=w,
                                       hops=hops, chunk=chunk,
                                       residual=residual, tau=tau)
        if cpad:
            mixed = mixed[:, :n]
            res_out = None if res_out is None else res_out[:, :n]
        return mixed, res_out

    g, n = x.shape
    bc = max(chunk, 1) * max(1, block_cols // max(chunk, 1))
    bc = min(bc, ((n + max(chunk, 1) - 1) // max(chunk, 1))
             * max(chunk, 1))
    pad = (-n) % bc
    padded = n + pad

    def padcols(a):
        return jnp.pad(a, ((0, 0), (0, pad))) if pad else a

    xs, x0s = padcols(x), padcols(x0)
    mean = w is None
    n_hops = 1 if (mean and kind != "thresh") else hops
    grid = (padded // bc,)
    in_specs = [pl.BlockSpec((g, bc), lambda i: (0, i)),
                pl.BlockSpec((g, bc), lambda i: (0, i))]
    args = [xs, x0s]
    if kind == "int8":
        # noise at the STAGED rows shape (G·N/chunk, chunk) keeps bits
        # identical; pad rows get fresh zeros (any noise quantizes a zero
        # chunk to zero — the value never reaches the real columns)
        u3 = u.reshape(n_hops, g, -1, chunk)
        if pad:
            u3 = jnp.pad(u3, ((0, 0), (0, 0), (0, pad // chunk), (0, 0)))
        args.append(u3)
        in_specs.append(pl.BlockSpec((n_hops, g, bc // chunk, chunk),
                                     lambda i: (0, 0, i, 0)))
    if not mean:
        args.append(jnp.asarray(w, jnp.float32))
        in_specs.append(pl.BlockSpec((g, g), lambda i: (0, 0)))
    if kind == "thresh":
        args += [padcols(residual), jnp.asarray(tau, jnp.float32)]
        in_specs += [pl.BlockSpec((g, bc), lambda i: (0, i)),
                     pl.BlockSpec((g, 1), lambda i: (0, 0))]

    ef = kind == "thresh"
    out_specs = pl.BlockSpec((g, bc), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((g, padded), jnp.float32)
    if ef:
        out_specs = (out_specs, pl.BlockSpec((g, bc), lambda i: (0, i)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((g, padded), jnp.float32))

    def kernel(*refs):
        it = iter(refs)
        x_b, x0_b = next(it)[...], next(it)[...]
        u_b = next(it)[...] if kind == "int8" else None
        w_b = None if mean else next(it)[...]
        res_b = next(it)[...] if ef else None
        tau_b = next(it)[...] if ef else None
        outs = list(it)
        mixed, res_out = _epilogue_block(kind, n_hops, chunk, x_b, x0_b,
                                         u_b, w_b, res_b, tau_b)
        outs[0][...] = mixed
        if ef:
            outs[1][...] = res_out

    out = named_pallas_call("exchange_epilogue", kernel, grid=grid,
                            in_specs=in_specs, out_specs=out_specs,
                            out_shape=out_shape, interpret=interpret)(*args)
    if ef:
        mixed, res_out = out
        return mixed[:, :n], res_out[:, :n]
    return out[:, :n], None


# ---------------------------------------------------------------------------
# shard-local fused quantize+dequantize (the shard_map exchange's codec)
# ---------------------------------------------------------------------------


def _qdq_kernel(x_ref, u_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.floor(x / scale + u_ref[...].astype(jnp.float32)),
                 -127.0, 127.0).astype(jnp.int8)
    o_ref[...] = q.astype(jnp.float32) * scale


def qdq_int8(x, u, *, interpret: bool = True):
    """(rows, chunk) f32 + uniform noise -> decoded (rows, chunk) f32 in
    ONE VMEM pass (the staged pair kernels/quantize.py quantize_int8 +
    dequantize_int8 re-reads every row; same math, same row blocks,
    bit-identical)."""
    rows, chunk = x.shape
    rb = row_block(rows)
    return named_pallas_call(
        "qdq_int8",
        _qdq_kernel,
        grid=(pl.cdiv(rows, rb),),
        in_specs=[pl.BlockSpec((rb, chunk), lambda i: (i, 0)),
                  pl.BlockSpec((rb, chunk), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), jnp.float32),
        interpret=interpret,
    )(x, u)
