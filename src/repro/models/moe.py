"""Mixture-of-Experts layer.

Two implementations selected by ``cfg.moe_impl``:

* ``grouped`` (the default) — no-drop grouped experts: the router's
  T*k (token, expert) pairs are sorted by expert, the token rows gathered
  into that order, each expert's SwiGLU runs as grouped matrix products
  over its contiguous (possibly empty) run of rows, and the rows are put
  back in pair order and summed per token with the gates. There is no
  capacity: every routed pair is computed, and the work is that of the k
  routed experts a token.
* ``densemask`` — every expert processes every token and the top-k gates
  mask the combination (a scan over experts): E/k times the routed work.
  Kept as the oracle the grouped layer is tested against.

Experts are stacked on a leading "experts" axis which shards over the
"model" mesh axis (16/16 for phi3.5-moe, 32/16 for granite-moe).

A standard auxiliary load-balance loss (Switch-style) is returned by the
router so training examples can regularize routing, and each forward
returns the expert load: the largest expert's share of the routed pairs
over the mean share (1 when balanced, at most E/k).
"""
from __future__ import annotations

import importlib
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.kernels import use_interpret
from repro.models.layers import pdef

# megablox's kernels (the package's own ``gmm`` name is their
# differentiable wrapper, which does not batch)
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")


def moe_defs(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    defs = {"w_router": pdef((d, e), ("embed", None))}
    if cfg.mlp_type == "swiglu":
        defs.update({
            "w_gate": pdef((e, d, f), ("experts", "embed", "ff")),
            "w_up": pdef((e, d, f), ("experts", "embed", "ff")),
            "w_down": pdef((e, f, d), ("experts", "ff", "embed")),
        })
    else:
        defs.update({
            "w_up": pdef((e, d, f), ("experts", "embed", "ff")),
            "w_down": pdef((e, f, d), ("experts", "ff", "embed")),
        })
    return defs


def _activate(p, x, cfg, matmul):
    """The expert FFN's hidden activation of rows x, with ``matmul(x,
    w_name)`` giving the product with that weight."""
    if cfg.mlp_type == "swiglu":
        return jax.nn.silu(matmul(x, "w_gate")) * matmul(x, "w_up")
    u = matmul(x, "w_up")
    return jnp.square(jax.nn.relu(u)) if cfg.mlp_type == "relu2" \
        else jax.nn.gelu(u)


def _expert_ffn(p, x, cfg, e):
    """Run expert e's FFN on x (..., D)."""
    dt = x.dtype
    h = _activate(p, x, cfg, lambda x, w: x @ p[w][e].astype(dt))
    return h @ p["w_down"][e].astype(dt)


def router(p, x, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (top-k gates (B,S,k), top-k indices (B,S,k), aux loss)."""
    logits = jnp.einsum("bsd,de->bse", x, p["w_router"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss.
    E = cfg.n_experts
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    one_hot = jax.nn.one_hot(idx.reshape(-1, cfg.top_k), E, dtype=jnp.float32)
    ce = jnp.mean(jnp.sum(one_hot, axis=1), axis=0) / cfg.top_k
    aux = E * jnp.sum(me * ce)
    return gates.astype(x.dtype), idx, aux


def expert_load(sizes) -> jax.Array:
    """The largest expert's share of the routed pairs over the mean share,
    from the pairs each expert got (E,)."""
    sizes = sizes.astype(jnp.float32)
    return jnp.max(sizes) * sizes.shape[0] / jnp.maximum(jnp.sum(sizes), 1.0)


def moe_densemask(p, x, cfg):
    """Oracle: scan over experts; every expert sees every token."""
    gates, idx, aux = router(p, x, cfg)
    # (B,S,E) combine weights scattered from the top-k selection.
    combine = jnp.zeros(x.shape[:2] + (cfg.n_experts,), x.dtype)
    b_idx = jnp.arange(x.shape[0])[:, None, None]
    s_idx = jnp.arange(x.shape[1])[None, :, None]
    combine = combine.at[b_idx, s_idx, idx].add(gates)

    def body(e, acc):
        y = _expert_ffn(p, x, cfg, e)
        return acc + combine[..., e, None] * y

    out = jax.lax.fori_loop(0, cfg.n_experts, body, jnp.zeros_like(x))
    load = expert_load(jnp.bincount(idx.reshape(-1), length=cfg.n_experts))
    return out, aux, load


# ---------------------------------------------------------------------------
# Grouped matrix products over rows sorted by expert
# ---------------------------------------------------------------------------
#
# ``grouped_matmul(x (M, K), w (E, K, N), sizes (E,))`` multiplies the
# rows of each contiguous run (sizes[0] rows by w[0], the next sizes[1]
# by w[1], ...) by its expert's matrix; the sizes sum to M. The products
# are the Pallas megablox kernels ``gmm`` and ``tgmm``, which measured
# faster than ``jax.lax.ragged_dot`` at the cell's shapes (PERF.md). Its
# vjp is two more grouped products: dx by the transposed weights, dw of
# each expert over its own rows. Under vmap (the round's groups) each
# product folds the mapped axis into the rows and the experts: the rows
# of mapped instance b follow those of b-1, so its runs follow too, and
# one grouped product over G*E experts computes all of them.


def _fold_rule(fn, out_lead):
    """A custom_vmap rule that runs ``fn`` once on the mapped axis folded
    into the leading axis of every operand; the result is split back
    along its ``out_lead`` leading axes."""
    def rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = fn(*[a.reshape((-1,) + a.shape[2:]) for a in args])
        return out.reshape((axis_size, -1) + out.shape[out_lead:]), True
    return rule


# megablox's tiles (rows, contracted, output columns), each cut to its
# dimension. On one TPU v5e at granite-train-g2t4's shapes (PERF.md),
# (512, 1024, 512) and (256, 512, 512) ran a layer's grouped products in
# 7.84 / 7.95 ms, (1024, 1024, 512) in 9.06, (128, 128, 128) in 37.7.
TILING = (512, 1024, 512)


def _tiling(m, k, n):
    return (min(TILING[0], m), min(TILING[1], k), min(TILING[2], n))


@custom_batching.custom_vmap
def _gmm(x, w, sizes):
    """(M, K) rows by their run's (K, N) matrix -> (M, N)."""
    return megablox.gmm(x, w, sizes, x.dtype, _tiling(*x.shape, w.shape[2]),
                        interpret=use_interpret())


_gmm.def_vmap(_fold_rule(_gmm.fun, 1))


@custom_batching.custom_vmap
def _tgmm(x, dy, sizes):
    """Each run's x^T dy: (M, K), (M, N) -> (E, K, N)."""
    return megablox.tgmm(x.swapaxes(0, 1), dy, sizes, x.dtype,
                         _tiling(*x.shape, dy.shape[1]),
                         interpret=use_interpret())


_tgmm.def_vmap(_fold_rule(_tgmm.fun, 1))


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    return _gmm(x, w, sizes)


def _grouped_matmul_fwd(x, w, sizes):
    return _gmm(x, w, sizes), (x, w, sizes)


def _grouped_matmul_bwd(res, dy):
    x, w, sizes = res
    dx = _gmm(dy, jnp.swapaxes(w, 1, 2), sizes)
    dw = _tgmm(x, dy, sizes).astype(w.dtype)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def moe_grouped(p, x, cfg):
    """No-drop grouped experts (module doc): (B,S,D) -> (out, aux, load)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    dt = x.dtype
    with jax.named_scope("router"):
        gates, idx, aux = router(p, x, cfg)
    with jax.named_scope("permute"):
        pair_expert = idx.reshape(-1)                     # (T*K,) token-major
        order = jnp.argsort(pair_expert, stable=True)     # pairs by expert
        sizes = jnp.bincount(pair_expert, length=E).astype(jnp.int32)
        rows = jnp.take(x.reshape(-1, D), order // K, axis=0)

    def matmul(r, w):
        return grouped_matmul(r, p[w].astype(dt), sizes)

    with jax.named_scope("experts"):
        y = matmul(_activate(p, rows, cfg, matmul), "w_down")
    with jax.named_scope("permute"):
        unsort = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.take(y, unsort, axis=0).reshape(B * S, K, D)
        out = jnp.einsum("tk,tkd->td", gates.reshape(-1, K), y)
    return out.reshape(B, S, D), aux, expert_load(sizes)


def moe_forward(p, x, cfg):
    """(B,S,D) -> (out, aux loss, expert load)."""
    if cfg.moe_impl == "densemask":
        return moe_densemask(p, x, cfg)
    if cfg.moe_impl != "grouped":
        raise ValueError(f"unknown moe_impl {cfg.moe_impl!r} "
                         "(have 'grouped', 'densemask')")
    return moe_grouped(p, x, cfg)


def moe_decode(p, x, cfg):
    """One-token MoE (B,1,D): gather the top-k expert weights per token and
    apply them directly — no capacity machinery needed at batch*1 scale."""
    gates, idx, aux = router(p, x, cfg)        # (B,1,K)
    B = x.shape[0]
    dt = x.dtype
    xe = x[:, 0]                               # (B,D)

    def one_expert(k):
        e = idx[:, 0, k]                       # (B,)
        if cfg.mlp_type == "swiglu":
            wg = p["w_gate"][e].astype(dt)     # (B,D,F)
            wu = p["w_up"][e].astype(dt)
            h = jax.nn.silu(jnp.einsum("bd,bdf->bf", xe, wg)) * \
                jnp.einsum("bd,bdf->bf", xe, wu)
        else:
            u = jnp.einsum("bd,bdf->bf", xe, p["w_up"][e].astype(dt))
            h = jnp.square(jax.nn.relu(u)) if cfg.mlp_type == "relu2" \
                else jax.nn.gelu(u)
        return jnp.einsum("bf,bfd->bd", h, p["w_down"][e].astype(dt))

    out = jnp.zeros_like(xe)
    for k in range(cfg.top_k):
        out = out + gates[:, 0, k, None] * one_expert(k)
    return out[:, None], aux
