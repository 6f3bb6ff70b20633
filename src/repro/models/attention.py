"""GQA attention: training/prefill (blocked causal, online softmax) and
decode (full KV cache or sliding-window ring buffer).

Variants supported per ArchConfig: qkv bias (qwen1.5, whisper, internvl2),
qk-norm (qwen3), explicit head_dim (qwen3), non-causal self attention
(whisper encoder), cross attention (whisper decoder).

Two prefill schedules over (q-block, kv-block) pairs:
  * ``rect``: all nb*nb pairs with causal masking — the simple baseline;
    computes the full S x S rectangle (2x causal-optimal FLOPs).
  * ``tri``: static lower-triangular pair list — causal-optimal FLOPs.
    This is the §Perf hillclimb schedule.
Both are pure-JAX analogues of the Pallas ``flash_attention`` kernel in
``repro.kernels`` (the TPU-target implementation of the same algorithm).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, head_rms_norm, pdef

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attention_defs(cfg):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    defs = {
        "wq": pdef((d, h, hd), ("embed", "heads", None)),
        "wk": pdef((d, kv, hd), ("embed", "kv_heads", None)),
        "wv": pdef((d, kv, hd), ("embed", "kv_heads", None)),
        "wo": pdef((h, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = pdef((h, hd), ("heads", None), init="zeros")
        defs["bk"] = pdef((kv, hd), ("kv_heads", None), init="zeros")
        defs["bv"] = pdef((kv, hd), ("kv_heads", None), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = pdef((hd,), (None,), init="ones")
        defs["k_norm"] = pdef((hd,), (None,), init="ones")
    return defs


def project_qkv(p, x, x_kv, cfg, positions, kv_positions, use_rope=True):
    """Returns q (B,Sq,H,hd), k/v (B,Sk,KV,hd)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x_kv, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x_kv, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def output_proj(p, attn_out):
    return jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"].astype(attn_out.dtype))


# ---------------------------------------------------------------------------
# Core attention math (GQA)
# ---------------------------------------------------------------------------


def _gqa_scores(q, k):
    """q (B,Sq,H,hd), k (B,Sk,KV,hd) -> scores (B,KV,G,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scale = jnp.asarray(1.0 / jnp.sqrt(hd), q.dtype)
    return jnp.einsum("bqkgh,bskh->bkgqs", qg, k) * scale


def _gqa_out(probs, v):
    """probs (B,KV,G,Sq,Sk), v (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    B, KV, G, Sq, _ = probs.shape
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, KV * G, v.shape[-1])


def full_attention(q, k, v, mask):
    """Unblocked path (short sequences / encoder). mask broadcastable to
    (Sq, Sk) bool, True = attend; mask=None means attend everywhere."""
    s = _gqa_scores(q, k).astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return _gqa_out(p, v)


# ---------------------------------------------------------------------------
# Blocked causal attention (online softmax over (qblk, kvblk) pairs)
# ---------------------------------------------------------------------------


def blocked_causal_attention(q, k, v, block: int, schedule: str = "tri"):
    """q,k,v over the same S (self attention), causal.

    Scans a static list of (q-block, kv-block) index pairs, maintaining
    online-softmax state for every query block. ``tri`` visits only the
    lower triangle (causal-optimal); ``rect`` visits all pairs and masks.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    assert S % block == 0, (S, block)
    nb = S // block

    if schedule == "tri":
        pairs = [(i, j) for i in range(nb) for j in range(i + 1)]
    elif schedule == "rect":
        pairs = [(i, j) for i in range(nb) for j in range(nb)]
    else:
        raise ValueError(schedule)
    qi = jnp.array([p[0] for p in pairs], jnp.int32)
    kj = jnp.array([p[1] for p in pairs], jnp.int32)

    qb = q.reshape(B, nb, block, H, hd)
    kb = k.reshape(B, nb, block, KV, hd)
    vb = v.reshape(B, nb, block, KV, hd)

    # Intra-block causal mask, used when i == j.
    tri_mask = jnp.tril(jnp.ones((block, block), bool))

    m0 = jnp.full((nb, B, KV, G, block), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nb, B, KV, G, block), jnp.float32)
    a0 = jnp.zeros((nb, B, block, H, hd), jnp.float32)

    def step(carry, idx):
        m, l, acc = carry
        i, j = idx
        qi_ = jnp.take(qb, i, axis=1)           # (B,block,H,hd)
        kj_ = jnp.take(kb, j, axis=1)
        vj_ = jnp.take(vb, j, axis=1)
        s = _gqa_scores(qi_, kj_).astype(jnp.float32)  # (B,KV,G,bq,bk)
        # mask: full if j<i, triangular if j==i, empty if j>i (rect only)
        keep = jnp.where(j < i, jnp.ones_like(tri_mask),
                         jnp.where(j == i, tri_mask, jnp.zeros_like(tri_mask)))
        s = jnp.where(keep[None, None, None], s, NEG_INF)
        mi, li, ai = m[i], l[i], acc[i]
        # The running max only keeps exp() in range; the result does not
        # depend on it, so it takes no gradient (as in jax.nn.softmax).
        # Differentiating it would divide by the count of scores equal to
        # their max, which is 0 where the remat's recomputed max and
        # scores round differently (bf16 scores on the TPU): NaN.
        m_new = jax.lax.stop_gradient(jnp.maximum(mi, jnp.max(s, axis=-1)))
        corr = jnp.exp(mi - m_new)
        pblk = jnp.exp(s - m_new[..., None])
        l_new = li * corr + jnp.sum(pblk, axis=-1)
        pv = _gqa_out(pblk.astype(q.dtype), vj_).astype(jnp.float32)
        corr_q = corr.transpose(0, 3, 1, 2).reshape(B, block, H)[..., None]
        a_new = ai * corr_q + pv
        return (m.at[i].set(m_new), l.at[i].set(l_new), acc.at[i].set(a_new)), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (qi, kj))
    l_q = l.transpose(0, 1, 4, 2, 3).reshape(nb, B, block, H)[..., None]
    out = acc / jnp.maximum(l_q, 1e-30)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# High-level forward (training / prefill / encoder / cross)
# ---------------------------------------------------------------------------


def pallas_causal_attention(q, k, v, block: int):
    """Route through the Pallas TPU flash kernel (repro.kernels).

    q (B,S,H,hd), k/v (B,S,KV,hd): GQA KV heads are repeated to H (the
    kernel streams KV blocks from VMEM, so the repeat is a view on TPU).
    Runs in interpret mode on CPU."""
    from repro.kernels import ops

    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(block, 128)
    out = ops.flash_attention(qt, kt, vt, block_q=bq, block_k=bq)
    return out.transpose(0, 2, 1, 3)


def attention_forward(p, x, cfg, *, causal=True, x_kv=None, use_rope=True,
                      positions=None, kv_positions=None,
                      schedule="tri", block=512, return_kv=False):
    """x (B,S,D) -> (B,S,D). Cross attention when x_kv is given.

    cfg.attn_impl selects the causal self-attention path: "blocked"
    (pure-JAX online-softmax scan — the dry-run/HLO path) or "pallas"
    (the VMEM-tiled TPU kernel, interpret-validated on CPU)."""
    B, S, _ = x.shape
    x_kv = x if x_kv is None else x_kv
    Sk = x_kv.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None]
    if kv_positions is None:
        kv_positions = jnp.arange(Sk)[None]
    q, k, v = project_qkv(p, x, x_kv, cfg, positions, kv_positions, use_rope)
    if causal and S == Sk and S % block == 0 and S // block >= 2 \
            and getattr(cfg, "attn_impl", "blocked") == "pallas" \
            and S % min(block, 128) == 0:
        out = pallas_causal_attention(q, k, v, block)
    elif causal and S == Sk and S % block == 0 and S // block >= 2:
        out = blocked_causal_attention(q, k, v, block, schedule)
    else:
        mask = None
        if causal:
            mask = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        out = full_attention(q, k, v, mask)
    y = output_proj(p, out)
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# Decode with KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, batch: int, cache_len: int, dtype):
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, kv, hd), dtype),
        "v": jnp.zeros((batch, cache_len, kv, hd), dtype),
        # absolute position held by each slot; -1 = empty
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),
    }


def kv_cache_shapes(cfg, batch: int, cache_len: int, dtype):
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, cache_len, kv, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, cache_len, kv, hd), dtype),
        "slot_pos": jax.ShapeDtypeStruct((cache_len,), jnp.int32),
    }


def decode_attention(p, x, cfg, cache, pos):
    """One-token decode. x (B,1,D); pos: scalar int32 absolute position.
    The cache is a ring buffer of length W (W >= context for decode_32k,
    W = sliding window for long_500k). RoPE is applied at absolute
    positions before caching, so ring overwrite is safe."""
    B = x.shape[0]
    W = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = project_qkv(p, x, x, cfg, positions, positions, True)
    slot = jnp.mod(pos, W)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    slot_pos = cache["slot_pos"].at[slot].set(pos)
    s = _gqa_scores(q, k).astype(jnp.float32)          # (B,KV,G,1,W)
    valid = slot_pos >= 0
    s = jnp.where(valid[None, None, None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = _gqa_out(probs, v)
    y = output_proj(p, out)
    return y, {"k": k, "v": v, "slot_pos": slot_pos}


def cross_attention_cache(p, enc_out, cfg):
    """Precompute cross-attention K/V from encoder output (whisper)."""
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return k, v


def cross_attention_decode(p, x, cfg, k, v):
    """One-token cross attention against fixed encoder K/V (no rope)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    s = _gqa_scores(q, k).astype(jnp.float32)
    probs = jax.nn.softmax(s, axis=-1).astype(dt)
    return output_proj(p, _gqa_out(probs, v))
