"""Model assembly: ArchConfig -> Model (init / forward / loss / prefill /
decode_step) for every assigned family.

Families:
  dense     embed -> [attn + mlp] x L -> norm -> lm_head
  moe       embed -> [attn + moe] x L
  hybrid    embed -> [mamba2] x L with one SHARED attention block applied
            every cfg.attn_every layers (zamba2)
  ssm       embed -> groups of (slstm_every-1 mLSTM + 1 sLSTM) (xlstm)
  vlm       stub patch embeddings prefixed to token embeddings -> dense stack
  audio     stub frame embeddings -> encoder stack; tokens -> decoder stack
            with cross attention (whisper)

All stacks scan over layers with stacked params + jax.checkpoint (bounded
HLO size and activation memory for 126-layer configs). The LM head / CE
loss is computed in sequence chunks (never materializes (B,S,V) logits).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import xlstm as xl
from repro.models.layers import (abstract_params, init_params, pdef, rms_norm,
                                 softmax_cross_entropy, stack_defs)

CE_CHUNK = 512
EMBED_CHUNK = 1024


# ---------------------------------------------------------------------------
# Embedding / LM head (vocab-sharded friendly)
# ---------------------------------------------------------------------------


def _embed_lookup(table, tokens, dtype, impl: str = "onehot"):
    """Embedding lookup.

    impl="onehot": one-hot matmul in sequence chunks — MXU-friendly and
    keeps a model-sharded vocab axis local, but costs 2*T*V*D flops
    (dominates useful flops for small-d / huge-vocab archs; §Perf).
    impl="gather": jnp.take — no flops; XLA resolves a sharded vocab axis
    with an all-gather of the (small, d-sharded) table slice or a
    distributed gather."""
    if impl == "gather":
        return jnp.take(table, tokens, axis=0).astype(dtype)
    B, S = tokens.shape
    V, D = table.shape
    chunk = min(EMBED_CHUNK, S)
    if S % chunk:
        chunk = S
    n = S // chunk
    tc = tokens.reshape(B, n, chunk)

    def step(_, tok):
        oh = jax.nn.one_hot(tok, V, dtype=dtype)
        return None, jnp.einsum("bsv,vd->bsd", oh, table.astype(dtype))

    _, out = jax.lax.scan(step, None, jnp.moveaxis(tc, 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, D)


def mask_padding(logits, vocab_size: int):
    """Logits over the padded table's rows with the rows from
    ``vocab_size`` on set to the lowest float, so that no softmax,
    logsumexp or argmax reads them; the logits as they are (no op) where
    the table has no padding."""
    V = logits.shape[-1]
    if V == vocab_size:
        return logits
    return jnp.where(jnp.arange(V) < vocab_size, logits,
                     jnp.finfo(logits.dtype).min)


def _chunked_ce(x, w_head, labels, mask=None, chunk=CE_CHUNK,
                vocab_size=None):
    """Mean next-token CE without materializing full logits.

    x (B,S,D) fp-activations, w_head (D,V), labels (B,S) int32. With
    ``vocab_size`` below V the softmax is over the first ``vocab_size``
    rows only (``mask_padding``).
    """
    B, S, D = x.shape
    if S % chunk:
        chunk = S
    n = S // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    if mask is None:
        mc = jnp.ones((n, B, chunk), jnp.float32)
    else:
        mc = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0).astype(jnp.float32)

    def step(acc, inp):
        xb, lb, mb = inp
        logits = jnp.einsum("bsd,dv->bsv", xb, w_head.astype(xb.dtype))
        logits = logits.astype(jnp.float32)
        if vocab_size is not None:
            logits = mask_padding(logits, vocab_size)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mb
        return (acc[0] + jnp.sum(nll), acc[1] + jnp.sum(mb)), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())),
                                 (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def _head_logits(x, head, cfg):
    """(B,S,D) -> float32 logits (B,S,padded V) over the published
    vocabulary (``mask_padding``)."""
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    return mask_padding(logits.astype(jnp.float32), cfg.vocab_size)


# ---------------------------------------------------------------------------
# Per-family block definitions
# ---------------------------------------------------------------------------


def _dense_block_defs(cfg):
    d = {"norm1": pdef((cfg.d_model,), ("embed",), init="ones"),
         "attn": attn.attention_defs(cfg),
         "norm2": pdef((cfg.d_model,), ("embed",), init="ones")}
    if cfg.is_moe:
        d["moe"] = moem.moe_defs(cfg)
    else:
        d["mlp"] = mlpm.mlp_defs(cfg)
    return d


def _dense_block(p, x, cfg, schedule, block):
    """One decoder layer: (new x, the layer's stats). The stats hold
    ``aux`` (the router's load-balance loss, 0 without experts) and, with
    experts, ``expert_load`` (``moe.expert_load``)."""
    h = attn.attention_forward(p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps),
                               cfg, schedule=schedule, block=block)
    x = x + h
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if cfg.is_moe:
        with jax.named_scope("moe"):
            y, aux, load = moem.moe_forward(p["moe"], h2, cfg)
        return x + y, {"aux": aux, "expert_load": load}
    y = mlpm.mlp_forward(p["mlp"], h2, cfg)
    return x + y, {"aux": jnp.zeros(())}


def _dense_block_decode(p, x, cfg, cache, pos):
    h, kv = attn.decode_attention(p["attn"],
                                  rms_norm(x, p["norm1"], cfg.norm_eps),
                                  cfg, cache, pos)
    x = x + h
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moem.moe_decode(p["moe"], h2, cfg)
    else:
        y = mlpm.mlp_forward(p["mlp"], h2, cfg)
    return x + y, kv


def _mamba_block(p, x, cfg):
    return x + mam.mamba_forward(p["mamba"],
                                 rms_norm(x, p["norm"], cfg.norm_eps), cfg)


def _mamba_block_decode(p, x, cfg, cache):
    y, new = mam.mamba_decode(p["mamba"],
                              rms_norm(x, p["norm"], cfg.norm_eps), cfg, cache)
    return x + y, new


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    defs: Any                                   # ParamDef tree
    # (params, batch) -> (x, aux, stats): stats are per-layer readings,
    # ``{"expert_load": (n_layers,)}`` for a model with experts, else {}
    forward: Callable
    decode_fn: Callable                         # (params, cache, tok, pos, extras)

    # -- params -------------------------------------------------------------
    def init(self, key):
        return init_params(self.defs, key)

    def abstract(self):
        return abstract_params(self.defs)

    # -- training -----------------------------------------------------------
    def loss(self, params, batch):
        return self.loss_and_stats(params, batch)[0]

    def loss_and_stats(self, params, batch):
        """(next-token CE over the published vocabulary + 0.01 * the
        routers' load-balance loss, the forward's stats)."""
        x, aux, stats = self.forward(params, batch)
        labels = batch["tokens"]
        lab = jnp.concatenate([labels[:, 1:],
                               jnp.zeros_like(labels[:, :1])], axis=1)
        mask = jnp.ones_like(lab, jnp.float32).at[:, -1].set(0.0)
        head = self._head(params)
        ce = _chunked_ce(x, head, lab, mask, vocab_size=self.cfg.vocab_size)
        return ce + 0.01 * aux, stats

    def logits(self, params, batch):
        """Full logits — for small/smoke configs only."""
        x = self.forward(params, batch)[0]
        return _head_logits(x, self._head(params), self.cfg)

    def _head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    # -- serving ------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, abstract=False,
                   extras: Optional[Dict] = None):
        dtype = jnp.dtype(self.cfg.dtype)
        return _build_cache(self.cfg, batch, cache_len, dtype,
                            abstract=abstract, extras=extras)

    def decode_step(self, params, cache, tokens, pos, extras=None):
        """tokens (B,1); pos scalar int32 -> (logits (B,1,V), new cache)."""
        return self.decode_fn(params, cache, tokens, pos, extras)


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _build_cache(cfg, batch, cache_len, dtype, abstract, extras=None):
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
         (lambda s, d: jnp.zeros(s, d))
    fam = cfg.family

    def kv(n_layers):
        c = attn.kv_cache_shapes(cfg, batch, cache_len, dtype)
        out = jax.tree.map(lambda s: mk((n_layers,) + s.shape, s.dtype), c)
        if not abstract:
            # empty slots marked -1
            out["slot_pos"] = jnp.full((n_layers, cache_len), -1, jnp.int32)
        return out

    if fam in ("dense", "moe", "vlm"):
        return {"kv": kv(cfg.n_layers)}
    if fam == "hybrid":
        mc = mam.mamba_cache_shapes(cfg, batch, dtype)
        mcache = jax.tree.map(
            lambda s: mk((cfg.n_layers,) + s.shape, s.dtype), mc)
        n_attn = cfg.n_layers // cfg.attn_every
        return {"mamba": mcache, "kv": kv(max(n_attn, 1))}
    if fam == "ssm":
        n_groups = cfg.n_layers // cfg.slstm_every
        n_m = cfg.slstm_every - 1
        ms = xl.mlstm_cache_shapes(cfg, batch, dtype)
        ss = xl.slstm_cache_shapes(cfg, batch, dtype)
        return {
            "mlstm": jax.tree.map(
                lambda s: mk((n_groups, n_m) + s.shape, s.dtype), ms),
            "slstm": jax.tree.map(
                lambda s: mk((n_groups,) + s.shape, s.dtype), ss),
        }
    if fam == "audio":
        kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        Ld = cfg.n_layers
        return {
            "kv": kv(Ld),
            "cross_k": mk((Ld, batch, cfg.n_frames, kvh, hd), dtype),
            "cross_v": mk((Ld, batch, cfg.n_frames, kvh, hd), dtype),
        }
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Builders per family
# ---------------------------------------------------------------------------


def _common_defs(cfg):
    defs = {
        "embed": pdef((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                      scale=0.02),
        "final_norm": pdef((cfg.d_model,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = pdef((cfg.d_model, cfg.padded_vocab),
                               ("embed", "vocab"))
    return defs


def build_model(cfg: ArchConfig, schedule: str = "tri",
                attn_block: int = 512, layer_param_hook=None,
                layer_act_hook=None) -> Model:
    """layer_param_hook(per_layer_params) -> per_layer_params is applied
    INSIDE the scan-over-layers body. The fsdp policy uses it to place a
    with_sharding_constraint that all-gathers one layer's weights at a
    time (params stay fsdp-sharded at rest; the transpose inserts the
    matching grad reduce-scatter). layer_act_hook(x) -> x likewise pins
    the activation sharding (batch over "fsdp") so propagation cannot
    un-shard the batch between layers."""
    fam = cfg.family
    if fam in ("dense", "moe"):
        return _build_decoder(cfg, schedule, attn_block, layer_param_hook,
                              layer_act_hook)
    if fam == "hybrid":
        return _build_hybrid(cfg, schedule, attn_block)
    if fam == "ssm":
        return _build_xlstm(cfg)
    if fam == "vlm":
        return _build_vlm(cfg, schedule, attn_block)
    if fam == "audio":
        return _build_whisper(cfg, schedule, attn_block)
    raise ValueError(fam)


# ---- dense / moe -----------------------------------------------------------


def _build_decoder(cfg, schedule, attn_block, layer_param_hook=None,
                   layer_act_hook=None):
    defs = _common_defs(cfg)
    defs["blocks"] = stack_defs(_dense_block_defs(cfg), cfg.n_layers)

    def forward(params, batch):
        tokens = batch["tokens"]
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)
        if layer_act_hook is not None:
            x = layer_act_hook(x)

        @jax.checkpoint
        def layer(x, p):
            if layer_param_hook is not None:
                p = layer_param_hook(p)
            if layer_act_hook is not None:
                x = layer_act_hook(x)
            x, st = _dense_block(p, x, cfg, schedule, attn_block)
            if layer_act_hook is not None:
                x = layer_act_hook(x)
            return x, st

        x, st = jax.lax.scan(layer, x, params["blocks"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        aux = jnp.sum(st.pop("aux"))
        return x, aux, st

    def decode(params, cache, tokens, pos, extras):
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)

        def layer(x, pc):
            p, c = pc
            x, kv = _dense_block_decode(p, x, cfg, c, pos)
            return x, kv

        x, new_kv = jax.lax.scan(layer, x, (params["blocks"], cache["kv"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return _head_logits(x, head, cfg), {"kv": new_kv}

    def prefill(params, batch, cache_len):
        """Batched prefill: ONE forward populates the KV cache for every
        prompt position (vs token-by-token decode). Returns
        (last-position logits (B,1,V), cache). Prompt length must be
        <= cache_len; positions land in ring slots pos % cache_len."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        assert S <= cache_len, (S, cache_len)
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)

        def layer(x, p):
            h, (k, v) = attn.attention_forward(
                p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg,
                schedule="tri", return_kv=True)
            x = x + h
            h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
            if cfg.is_moe:
                y, _, _ = moem.moe_forward(p["moe"], h2, cfg)
            else:
                y = mlpm.mlp_forward(p["mlp"], h2, cfg)
            return x + y, (k, v)

        x, (ks, vs) = jax.lax.scan(layer, x, params["blocks"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = _head_logits(x[:, -1:], head, cfg)
        pad = cache_len - S
        kc = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        slot = jnp.pad(jnp.arange(S, dtype=jnp.int32), (0, pad),
                       constant_values=-1)
        slot_pos = jnp.broadcast_to(slot, (cfg.n_layers, cache_len))
        return logits, {"kv": {"k": kc, "v": vc, "slot_pos": slot_pos}}

    m = Model(cfg, defs, forward, decode)
    m.prefill = prefill
    return m


# ---- hybrid (zamba2) --------------------------------------------------------


def _build_hybrid(cfg, schedule, attn_block):
    defs = _common_defs(cfg)
    defs["blocks"] = stack_defs(
        {"norm": pdef((cfg.d_model,), ("embed",), init="ones"),
         "mamba": mam.mamba_defs(cfg)}, cfg.n_layers)
    # one SHARED attention block (zamba2's signature trick)
    defs["shared_attn"] = {
        "norm": pdef((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn.attention_defs(cfg),
    }
    every = cfg.attn_every

    def forward(params, batch):
        tokens = batch["tokens"]
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)
        sh = params["shared_attn"]

        @jax.checkpoint
        def layer(carry, inp):
            x = carry
            p, idx = inp
            x = _mamba_block(p, x, cfg)
            use_attn = (idx % every) == (every - 1)

            def with_attn(x):
                h = attn.attention_forward(
                    sh["attn"], rms_norm(x, sh["norm"], cfg.norm_eps), cfg,
                    schedule=schedule, block=attn_block)
                return x + h

            x = jax.lax.cond(use_attn, with_attn, lambda x: x, x)
            return x, None

        idxs = jnp.arange(cfg.n_layers)
        x, _ = jax.lax.scan(layer, x, (params["blocks"], idxs))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.zeros(()), {}

    def decode(params, cache, tokens, pos, extras):
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)
        sh = params["shared_attn"]
        n_attn = max(cfg.n_layers // every, 1)

        def layer(carry, inp):
            x, kvs = carry
            p, mc, idx = inp
            x, new_mc = _mamba_block_decode(p, x, cfg, mc)
            use_attn = (idx % every) == (every - 1)
            slot = jnp.minimum(idx // every, n_attn - 1)
            kv_l = jax.tree.map(lambda a: a[slot], kvs)

            def with_attn(args):
                x, kv_l = args
                h, new_kv = attn.decode_attention(
                    sh["attn"], rms_norm(x, sh["norm"], cfg.norm_eps), cfg,
                    kv_l, pos)
                return x + h, new_kv

            x, kv_l = jax.lax.cond(use_attn, with_attn, lambda a: a,
                                   (x, kv_l))
            kvs = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_index_in_dim(
                    full, one, slot, 0), kvs, kv_l)
            return (x, kvs), new_mc

        idxs = jnp.arange(cfg.n_layers)
        (x, new_kvs), new_mamba = jax.lax.scan(
            layer, (x, cache["kv"]), (params["blocks"], cache["mamba"], idxs))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return _head_logits(x, head, cfg), {"mamba": new_mamba, "kv": new_kvs}

    return Model(cfg, defs, forward, decode)


# ---- ssm (xlstm) ------------------------------------------------------------


def _build_xlstm(cfg):
    n_groups = cfg.n_layers // cfg.slstm_every
    n_m = cfg.slstm_every - 1
    defs = _common_defs(cfg)
    m_defs = {"norm": pdef((cfg.d_model,), ("embed",), init="ones"),
              "cell": xl.mlstm_defs(cfg)}
    s_defs = {"norm": pdef((cfg.d_model,), ("embed",), init="ones"),
              "cell": xl.slstm_defs(cfg)}
    defs["mlstm"] = stack_defs(stack_defs(m_defs, n_m, "sub"), n_groups)
    defs["slstm"] = stack_defs(s_defs, n_groups)

    def forward(params, batch):
        tokens = batch["tokens"]
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)

        @jax.checkpoint
        def group(x, p):
            def msub(x, pm):
                h = rms_norm(x, pm["norm"], cfg.norm_eps)
                return x + xl.mlstm_forward(pm["cell"], h, cfg), None

            x, _ = jax.lax.scan(msub, x, p["m"])
            h = rms_norm(x, p["s"]["norm"], cfg.norm_eps)
            x = x + xl.slstm_forward(p["s"]["cell"], h, cfg)
            return x, None

        x, _ = jax.lax.scan(group, x, {"m": params["mlstm"],
                                       "s": params["slstm"]})
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.zeros(()), {}

    def decode(params, cache, tokens, pos, extras):
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)

        def group(x, inp):
            p, mc, sc = inp

            def msub(x, pc):
                pm, c = pc
                h = rms_norm(x, pm["norm"], cfg.norm_eps)
                y, new = xl.mlstm_decode(pm["cell"], h, cfg, c)
                return x + y, new

            x, new_mc = jax.lax.scan(msub, x, (p["m"], mc))
            h = rms_norm(x, p["s"]["norm"], cfg.norm_eps)
            y, new_sc = xl.slstm_decode(p["s"]["cell"], h, cfg, sc)
            return x + y, (new_mc, new_sc)

        x, (new_m, new_s) = jax.lax.scan(
            group, x, ({"m": params["mlstm"], "s": params["slstm"]},
                       cache["mlstm"], cache["slstm"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return _head_logits(x, head, cfg), {"mlstm": new_m, "slstm": new_s}

    return Model(cfg, defs, forward, decode)


# ---- vlm (internvl2) ---------------------------------------------------------


def _build_vlm(cfg, schedule, attn_block):
    base = _build_decoder(cfg, schedule, attn_block)
    defs = dict(base.defs)
    # projector applied to the stub ViT patch embeddings
    defs["projector"] = {
        "w": pdef((cfg.d_model, cfg.d_model), ("embed", None)),
        "b": pdef((cfg.d_model,), (None,), init="zeros"),
    }

    def forward(params, batch):
        tokens = batch["tokens"]
        dtype = jnp.dtype(cfg.dtype)
        tok = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)
        if "patches" in batch:
            pr = params["projector"]
            pe = (batch["patches"].astype(dtype) @ pr["w"].astype(dtype)
                  + pr["b"].astype(dtype))
            # patch prefix replaces the first n_patches token slots so the
            # sequence length (and position ids) stay fixed for sharding.
            n = pe.shape[1]
            x = jnp.concatenate([pe, tok[:, n:]], axis=1)
        else:
            x = tok

        @jax.checkpoint
        def layer(x, p):
            x, st = _dense_block(p, x, cfg, schedule, attn_block)
            return x, st["aux"]

        x, auxs = jax.lax.scan(layer, x, params["blocks"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.sum(auxs), {}

    return Model(cfg, defs, forward, base.decode_fn)


# ---- audio (whisper) ----------------------------------------------------------


def _build_whisper(cfg, schedule, attn_block):
    defs = _common_defs(cfg)
    enc_block = {
        "norm1": pdef((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn.attention_defs(cfg),
        "norm2": pdef((cfg.d_model,), ("embed",), init="ones"),
        "mlp": mlpm.mlp_defs(cfg),
    }
    dec_block = {
        "norm1": pdef((cfg.d_model,), ("embed",), init="ones"),
        "self_attn": attn.attention_defs(cfg),
        "norm2": pdef((cfg.d_model,), ("embed",), init="ones"),
        "cross_attn": attn.attention_defs(cfg),
        "norm3": pdef((cfg.d_model,), ("embed",), init="ones"),
        "mlp": mlpm.mlp_defs(cfg),
    }
    defs["enc"] = stack_defs(enc_block, cfg.n_encoder_layers)
    defs["dec"] = stack_defs(dec_block, cfg.n_layers)
    defs["enc_norm"] = pdef((cfg.d_model,), ("embed",), init="ones")
    # projector on the stub conv/mel frame embeddings
    defs["frame_proj"] = {
        "w": pdef((cfg.d_model, cfg.d_model), ("embed", None)),
        "b": pdef((cfg.d_model,), (None,), init="zeros"),
    }

    def encode(params, frames):
        dtype = jnp.dtype(cfg.dtype)
        fp = params["frame_proj"]
        x = (frames.astype(dtype) @ fp["w"].astype(dtype)
             + fp["b"].astype(dtype))

        @jax.checkpoint
        def layer(x, p):
            h = attn.attention_forward(
                p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg,
                causal=False, use_rope=False)
            x = x + h
            x = x + mlpm.mlp_forward(p["mlp"],
                                     rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
            return x, None

        x, _ = jax.lax.scan(layer, x, params["enc"])
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def forward(params, batch):
        enc_out = encode(params, batch["frames"])
        tokens = batch["tokens"]
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)

        @jax.checkpoint
        def layer(x, p):
            h = attn.attention_forward(
                p["self_attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg,
                schedule=schedule, block=attn_block)
            x = x + h
            h = attn.attention_forward(
                p["cross_attn"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg,
                causal=False, x_kv=enc_out, use_rope=False)
            x = x + h
            x = x + mlpm.mlp_forward(p["mlp"],
                                     rms_norm(x, p["norm3"], cfg.norm_eps), cfg)
            return x, None

        x, _ = jax.lax.scan(layer, x, params["dec"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.zeros(()), {}

    def decode(params, cache, tokens, pos, extras):
        dtype = jnp.dtype(cfg.dtype)
        x = _embed_lookup(params["embed"], tokens, dtype, cfg.embed_impl)

        def layer(x, pc):
            p, kv_l, ck, cv = pc
            h, new_kv = attn.decode_attention(
                p["self_attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg,
                kv_l, pos)
            x = x + h
            h = attn.cross_attention_decode(
                p["cross_attn"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg,
                ck, cv)
            x = x + h
            x = x + mlpm.mlp_forward(p["mlp"],
                                     rms_norm(x, p["norm3"], cfg.norm_eps), cfg)
            return x, new_kv

        x, new_kv = jax.lax.scan(
            layer, x, (params["dec"], cache["kv"],
                       cache["cross_k"], cache["cross_v"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return _head_logits(x, head, cfg), {
            "kv": new_kv, "cross_k": cache["cross_k"],
            "cross_v": cache["cross_v"]}

    m = Model(cfg, defs, forward, decode)
    m.encode = partial_encode(encode)
    return m


def partial_encode(fn):
    return fn
