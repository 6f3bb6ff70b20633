"""Fixed-shape jit decode/prefill programs over the paged pool.

One compiled ``step`` per (config, batch geometry) serves EVERY decode
step of the engine's life: admissions and retirements never change a
shape. The scheduler ships plain arrays each call — tokens (B,),
per-slot positions (B,), page tables (B, layers_kv, max_blocks), state
rows (B, state_rows), and an ``active`` mask (B,) — and inactive slots
run the same program against the trash page (pos 0, length 1, rows 0):
finite garbage, never read by an active slot (every op in every family's
decode path is batch-elementwise over slots, which is what makes the
continuous-vs-isolated parity tests exact).

Per family:
  dense/moe  per-layer paged KV; attention through the decode kernel
             (``kernels/decode_attention``, impl-resolved pallas/jnp);
             batched prefill (one ``attention_forward`` pass per layer,
             right-padded to the prompt bucket — causal-safe) scatters
             whole pages.
  hybrid     mamba state rows + the zamba2 SHARED attention block's
             n_attn paged KV tables; prefill is a masked scan of the
             same per-token core (recurrence is inherently stepwise), so
             prefill-vs-stepwise parity is exact by construction.
  ssm        pure state rows (no KV pages); the model's own decode_fn is
             the token core; masked-scan prefill likewise.
  vlm/audio  REFUSED: their decode needs modality extras (patches /
             encoder frames) outside the token-slot contract.

Recurrent state lives in the pool as packed flat buffers (one
``optim/packing`` Layout per config, slot-major) — freed pages are
recycled dirty, so prefill starts from a zeros buffer, never from the
pool.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as da
from repro.kernels import resolve_impl, use_interpret
from repro.models import api as mapi
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import xlstm as xl
from repro.models.layers import rms_norm
from repro.optim.packing import Layout, layout_of, pack, unpack
from repro.serve.paging import (PageGeom, make_geom, read_state,
                                write_prefill_kv, write_state,
                                write_token_kv)

SERVE_FAMILIES = ("dense", "moe", "hybrid", "ssm")


def _refuse(fam: str):
    raise NotImplementedError(
        f"serve does not support family {fam!r}: its decode path needs "
        "per-request modality inputs (vlm patches / audio encoder frames) "
        "outside the engine's token-slot contract — serve a "
        "dense/moe/hybrid/ssm config instead, or drive this family's "
        "generation directly through Model.decode_step (static batch, "
        "no scheduler)")


def _greedy(logits, vocab: int):
    """(B, padded_vocab) f32 -> (B,) int32 greedy tokens (pad vocab
    entries excluded)."""
    return jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)


def _logits(params, x, cfg):
    """Final norm + LM head on the single decode position: x (B,1,D) ->
    (B, padded_vocab) f32."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    out = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    return out.astype(jnp.float32)[:, 0]


# ---------------------------------------------------------------------------
# State layouts (recurrent families)
# ---------------------------------------------------------------------------


def state_layout_for(model) -> Optional[Layout]:
    """packing.Layout of ONE slot's recurrent-state pytree (no batch
    axis; packs with a leading B axis to (B, size)). None for pure-KV
    families."""
    cfg = model.cfg
    dtype = jnp.dtype(cfg.dtype)
    fam = cfg.family
    if fam in ("dense", "moe"):
        return None
    if fam == "hybrid":
        mc = mam.mamba_cache_shapes(cfg, 1, dtype)
        spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.n_layers,) + s.shape[1:],
                                           s.dtype), mc)
        return layout_of(spec)
    if fam == "ssm":
        n_groups = cfg.n_layers // cfg.slstm_every
        n_m = cfg.slstm_every - 1
        ms = xl.mlstm_cache_shapes(cfg, 1, dtype)
        ss = xl.slstm_cache_shapes(cfg, 1, dtype)
        spec = {
            "mlstm": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (n_groups, n_m) + s.shape[1:], s.dtype), ms),
            "slstm": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (n_groups,) + s.shape[1:], s.dtype), ss),
        }
        return layout_of(spec)
    _refuse(fam)


def _to_slot_major(fam, tree):
    """Device-cache axis order -> slot-major (B leading on every leaf),
    so each slot's state is one contiguous packed buffer."""
    if fam == "hybrid":                       # (L, B, ...) -> (B, L, ...)
        return jax.tree.map(lambda l: jnp.moveaxis(l, 1, 0), tree)
    return {"mlstm": jax.tree.map(lambda l: jnp.moveaxis(l, 2, 0),
                                  tree["mlstm"]),
            "slstm": jax.tree.map(lambda l: jnp.moveaxis(l, 1, 0),
                                  tree["slstm"])}


def _from_slot_major(fam, tree):
    if fam == "hybrid":                       # (B, L, ...) -> (L, B, ...)
        return jax.tree.map(lambda l: jnp.moveaxis(l, 0, 1), tree)
    return {"mlstm": jax.tree.map(lambda l: jnp.moveaxis(l, 0, 2),
                                  tree["mlstm"]),
            "slstm": jax.tree.map(lambda l: jnp.moveaxis(l, 0, 1),
                                  tree["slstm"])}


def _zero_state(fam, layout, batch: int):
    """Fresh per-slot state in device-cache axis order — from a zeros
    buffer, NEVER from the pool (freed rows are recycled dirty)."""
    return _from_slot_major(
        fam, unpack(jnp.zeros((batch, layout.size), jnp.float32), layout))


# ---------------------------------------------------------------------------
# Geometry / attention-impl resolution
# ---------------------------------------------------------------------------


def geom_for(model, *, n_slots: int, page_size: int, max_len: int,
             slack_slots: int = 0, n_pages: Optional[int] = None) -> PageGeom:
    cfg = model.cfg
    fam = cfg.family
    if fam not in SERVE_FAMILIES:
        _refuse(fam)
    layout = state_layout_for(model)
    if fam in ("dense", "moe"):
        n_layers_kv = cfg.n_layers
    elif fam == "hybrid":
        n_layers_kv = max(cfg.n_layers // cfg.attn_every, 1)
    else:
        n_layers_kv = 0
    return make_geom(
        page_size=page_size,
        n_kv=cfg.n_kv_heads if n_layers_kv else 0,
        head_dim=cfg.resolved_head_dim if n_layers_kv else 0,
        n_layers_kv=n_layers_kv, max_len=max_len,
        state_size=layout.size if layout is not None else 0,
        n_slots=n_slots, slack_slots=slack_slots, n_pages=n_pages)


def _make_attn(impl: str, geom: PageGeom):
    """Decode-attention callable, impl-resolved the same way as every
    other kernel front (kernels.resolve_impl): jnp reference off-TPU
    under "auto"; an explicit "pallas" on an unsupported backend raises."""
    impl = resolve_impl(impl)
    ps, n_kv = geom.page_size, geom.n_kv
    if impl == "pallas":
        interp = use_interpret()

        def f(q, pool, rk, rv, lengths):
            return da.paged_decode_attention(
                q, pool, rk, rv, lengths, page_size=ps, n_kv=n_kv,
                interpret=interp)
        return f

    def f(q, pool, rk, rv, lengths):
        return da.paged_decode_attention_ref(
            q, pool, rk, rv, lengths, page_size=ps, n_kv=n_kv)
    return f


# ---------------------------------------------------------------------------
# Per-family program builders
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Programs:
    """The two jit'd entry points the engine drives (pool donated):

    step(params, pool, tokens (B,), pos (B,), rows_k, rows_v
         (B, layers_kv, max_blocks), srows (B, state_rows), active (B,))
      -> (greedy tokens (B,) int32, pool)
    prefill(params, pool, tokens (1, P), length, rows_k, rows_v
            (layers_kv, max_blocks), srows (state_rows,))
      -> (first generated token (1,) int32, pool)

    Unused arguments per family (srows for dense, page tables for ssm)
    are accepted and ignored so the engine stays family-agnostic.
    """
    family: str
    geom: PageGeom
    state_layout: Optional[Layout]
    step: Callable
    prefill: Callable


def _build_decoder_programs(model, geom, attn_fn):
    cfg = model.cfg
    eps = cfg.norm_eps
    dtype = jnp.dtype(cfg.dtype)
    ps = geom.page_size

    def step(params, pool, tokens, pos, rows_k, rows_v, srows, active):
        x = mapi._embed_lookup(params["embed"], tokens[:, None], dtype,
                               cfg.embed_impl)
        positions = pos[:, None]
        blk, off, lengths = pos // ps, pos % ps, pos + 1

        def layer(carry, inp):
            x, pool = carry
            p, rk, rv = inp
            h = rms_norm(x, p["norm1"], eps)
            q, k, v = attn.project_qkv(p["attn"], h, h, cfg, positions,
                                       positions, True)
            pool = write_token_kv(pool, rk, blk, off, k[:, 0], active)
            pool = write_token_kv(pool, rv, blk, off, v[:, 0], active)
            a = attn_fn(q[:, 0], pool, rk, rv, lengths)
            x = x + attn.output_proj(p["attn"], a[:, None].astype(x.dtype))
            h2 = rms_norm(x, p["norm2"], eps)
            if cfg.is_moe:
                y, _ = moem.moe_decode(p["moe"], h2, cfg)
            else:
                y = mlpm.mlp_forward(p["mlp"], h2, cfg)
            return (x + y, pool), None

        (x, pool), _ = jax.lax.scan(
            layer, (x, pool),
            (params["blocks"], jnp.moveaxis(rows_k, 1, 0),
             jnp.moveaxis(rows_v, 1, 0)))
        return _greedy(_logits(params, x, cfg), cfg.vocab_size), pool

    def prefill(params, pool, tokens, length, rows_k, rows_v, srows):
        # Batched prefill: prompt right-padded to the static bucket P
        # (a page multiple). Padding positions are causal-safe (real
        # token t only attends to indices <= t < length) and their page
        # garbage is hidden by length masking at decode time.
        _, P = tokens.shape
        nblk_p = P // ps
        x = mapi._embed_lookup(params["embed"], tokens, dtype,
                               cfg.embed_impl)

        def layer(carry, inp):
            x, pool = carry
            p, rk, rv = inp
            h, (k, v) = attn.attention_forward(
                p["attn"], rms_norm(x, p["norm1"], eps), cfg,
                schedule="tri", return_kv=True)
            x = x + h
            h2 = rms_norm(x, p["norm2"], eps)
            if cfg.is_moe:
                y, _, _ = moem.moe_forward(p["moe"], h2, cfg)
            else:
                y = mlpm.mlp_forward(p["mlp"], h2, cfg)
            pool = write_prefill_kv(pool, rk[:nblk_p],
                                    k.reshape(nblk_p, ps, *k.shape[2:]))
            pool = write_prefill_kv(pool, rv[:nblk_p],
                                    v.reshape(nblk_p, ps, *v.shape[2:]))
            return (x + y, pool), None

        (x, pool), _ = jax.lax.scan(layer, (x, pool),
                                    (params["blocks"], rows_k, rows_v))
        last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1)
        return _greedy(_logits(params, last, cfg), cfg.vocab_size), pool

    return step, prefill


def _build_hybrid_programs(model, geom, attn_fn, layout):
    cfg = model.cfg
    eps = cfg.norm_eps
    dtype = jnp.dtype(cfg.dtype)
    ps = geom.page_size
    every = cfg.attn_every
    n_attn = max(cfg.n_layers // every, 1)
    fam = "hybrid"

    def token(params, pool, state, tokens, pos, rows_k, rows_v, active):
        """One token for the whole batch: state leaves (L, B, ...)."""
        x = mapi._embed_lookup(params["embed"], tokens[:, None], dtype,
                               cfg.embed_impl)
        sh = params["shared_attn"]
        positions = pos[:, None]
        blk, off, lengths = pos // ps, pos % ps, pos + 1

        def layer(carry, inp):
            x, pool = carry
            p, mc, idx = inp
            x, new_mc = mapi._mamba_block_decode(p, x, cfg, mc)
            use_attn = (idx % every) == (every - 1)
            slot = jnp.minimum(idx // every, n_attn - 1)
            rk = jnp.take(rows_k, slot, axis=1)
            rv = jnp.take(rows_v, slot, axis=1)

            def with_attn(args):
                x, pool = args
                h = rms_norm(x, sh["norm"], eps)
                q, k, v = attn.project_qkv(sh["attn"], h, h, cfg,
                                           positions, positions, True)
                pool = write_token_kv(pool, rk, blk, off, k[:, 0],
                                      active)
                pool = write_token_kv(pool, rv, blk, off, v[:, 0],
                                      active)
                a = attn_fn(q[:, 0], pool, rk, rv, lengths)
                y = attn.output_proj(sh["attn"], a[:, None].astype(x.dtype))
                return x + y, pool

            x, pool = jax.lax.cond(use_attn, with_attn, lambda a: a,
                                   (x, pool))
            return (x, pool), new_mc

        idxs = jnp.arange(cfg.n_layers)
        (x, pool), new_state = jax.lax.scan(
            layer, (x, pool), (params["blocks"], state, idxs))
        return (_greedy(_logits(params, x, cfg), cfg.vocab_size), pool,
                new_state)

    def step(params, pool, tokens, pos, rows_k, rows_v, srows, active):
        buf = read_state(pool, srows, layout.size)
        state = _from_slot_major(fam, unpack(buf, layout))
        tok, pool, new_state = token(params, pool, state, tokens, pos,
                                     rows_k, rows_v, active)
        buf = pack(_to_slot_major(fam, new_state), layout)
        pool = write_state(pool, srows, buf, active)
        return tok, pool

    def prefill(params, pool, tokens, length, rows_k, rows_v, srows):
        # Masked scan of the SAME per-token core as step: pad steps
        # route kv to trash and leave state untouched, so prefill is
        # bit-equal to feeding the prompt token-by-token.
        _, P = tokens.shape
        state0 = _zero_state(fam, layout, 1)
        rk, rv = rows_k[None], rows_v[None]

        def pstep(carry, t):
            pool, state, tok_hold = carry
            valid = t < length
            tok_t = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)[:, 0]
            pos = jnp.full((1,), t, jnp.int32)
            tok, pool, new_state = token(params, pool, state, tok_t, pos,
                                         rk, rv, valid[None])
            state = jax.tree.map(lambda n, o: jnp.where(valid, n, o),
                                 new_state, state)
            tok_hold = jnp.where(t == length - 1, tok[0], tok_hold)
            return (pool, state, tok_hold), None

        (pool, state, tok_hold), _ = jax.lax.scan(
            pstep, (pool, state0, jnp.zeros((), jnp.int32)),
            jnp.arange(P, dtype=jnp.int32))
        buf = pack(_to_slot_major(fam, state), layout)
        pool = write_state(pool, srows[None], buf)
        return tok_hold[None], pool

    return step, prefill


def _build_ssm_programs(model, geom, layout):
    cfg = model.cfg
    fam = "ssm"

    def core(params, cache, tokens):
        logits, new_cache = model.decode_fn(params, cache, tokens, 0, None)
        return logits[:, 0], new_cache

    def step(params, pool, tokens, pos, rows_k, rows_v, srows, active):
        buf = read_state(pool, srows, layout.size)
        cache = _from_slot_major(fam, unpack(buf, layout))
        logits, new_cache = core(params, cache, tokens[:, None])
        buf = pack(_to_slot_major(fam, new_cache), layout)
        pool = write_state(pool, srows, buf, active)
        return _greedy(logits, cfg.vocab_size), pool

    def prefill(params, pool, tokens, length, rows_k, rows_v, srows):
        _, P = tokens.shape
        cache0 = _zero_state(fam, layout, 1)

        def pstep(carry, t):
            cache, tok_hold = carry
            tok_t = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            logits, new_cache = core(params, cache, tok_t)
            valid = t < length
            cache = jax.tree.map(lambda n, o: jnp.where(valid, n, o),
                                 new_cache, cache)
            tok_hold = jnp.where(t == length - 1,
                                 _greedy(logits, cfg.vocab_size)[0],
                                 tok_hold)
            return (cache, tok_hold), None

        (cache, tok_hold), _ = jax.lax.scan(
            pstep, (cache0, jnp.zeros((), jnp.int32)),
            jnp.arange(P, dtype=jnp.int32))
        buf = pack(_to_slot_major(fam, cache), layout)
        pool = write_state(pool, srows[None], buf)
        return tok_hold[None], pool

    return step, prefill


def build_programs(model, geom: PageGeom, impl: str = "auto") -> Programs:
    fam = model.cfg.family
    if fam not in SERVE_FAMILIES:
        _refuse(fam)
    resolve_impl(impl)    # surface bad impl strings / unsupported pallas
    layout = state_layout_for(model)
    if fam in ("dense", "moe"):
        step, prefill = _build_decoder_programs(model, geom,
                                                _make_attn(impl, geom))
    elif fam == "hybrid":
        step, prefill = _build_hybrid_programs(model, geom,
                                               _make_attn(impl, geom),
                                               layout)
    else:
        step, prefill = _build_ssm_programs(model, geom, layout)
    return Programs(family=fam, geom=geom, state_layout=layout,
                    step=jax.jit(step, donate_argnums=(1,)),
                    prefill=jax.jit(prefill, donate_argnums=(1,)))
