"""Plain reference of a decoder-only transformer (dense SwiGLU or top-k
mixture of experts), its loss, local-SGD rounds and full-sequence logits.

Straightforward ``jax.numpy``: a token lookup, RMSNorm, rotary positions
(half-split), full causal GQA attention, SwiGLU or a softmax router over
all experts with the top-k gates renormalised, final norm, output head,
and next-token cross entropy. It imports nothing of the program; it is
given the configuration's sizes and weights made by ``common.make_weights``
from the same seed.

``mode`` sets the precision: ``"f32"`` is float32 with every matrix
product at full precision (the reference); ``"bf16"`` computes in
bfloat16 and ``"fp8"`` rounds every product's operands to float8 (e4m3)
over bfloat16 activations (the controls for float32 and bfloat16
configurations).
"""
from __future__ import annotations

import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np

AUX_WEIGHT = 0.01   # weight of the router's load-balance term in the loss


def padded_vocab(arch: dict) -> int:
    return 256 * (-(-arch["vocab_size"] // 256))


def head_dim(arch: dict) -> int:
    return arch.get("head_dim") or arch["d_model"] // arch["n_heads"]


def param_specs(arch: dict) -> dict:
    """``{path: (shape, kind)}`` of the weights, in the program's naming:
    the blocks are stacked on a leading layer axis, the token table has
    the vocabulary padded to a multiple of 256 rows."""
    if arch.get("qkv_bias") or arch.get("qk_norm"):
        raise NotImplementedError("qkv bias / qk-norm are not in this "
                                  "reference")
    if arch.get("mlp_type", "swiglu") != "swiglu":
        raise NotImplementedError("only SwiGLU feed-forward layers")
    L, d = arch["n_layers"], arch["d_model"]
    H, KV, hd = arch["n_heads"], arch["n_kv_heads"], head_dim(arch)
    f, V = arch["d_ff"], padded_vocab(arch)
    s = {"embed": ((V, d), "normal"), "final_norm": ((d,), "ones"),
         "blocks/norm1": ((L, d), "ones"), "blocks/norm2": ((L, d), "ones"),
         "blocks/attn/wq": ((L, d, H, hd), "normal"),
         "blocks/attn/wk": ((L, d, KV, hd), "normal"),
         "blocks/attn/wv": ((L, d, KV, hd), "normal"),
         "blocks/attn/wo": ((L, H, hd, d), "normal")}
    if not arch.get("tie_embeddings"):
        s["lm_head"] = ((d, V), "normal")
    E = arch.get("n_experts", 0)
    if E:
        s.update({"blocks/moe/w_router": ((L, d, E), "normal"),
                  "blocks/moe/w_gate": ((L, E, d, f), "normal"),
                  "blocks/moe/w_up": ((L, E, d, f), "normal"),
                  "blocks/moe/w_down": ((L, E, f, d), "normal")})
    else:
        s.update({"blocks/mlp/w_gate": ((L, d, f), "normal"),
                  "blocks/mlp/w_up": ((L, d, f), "normal"),
                  "blocks/mlp/w_down": ((L, f, d), "normal")})
    return s


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------


def _act_dtype(mode: str):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _precision(mode: str):
    if mode == "f32":
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def _mm(spec: str, a, b, mode: str):
    """A matrix product in the mode's precision, result in the
    activation dtype. In the lower modes the operands are rounded to
    bfloat16 (or float8 e4m3) and the products summed in float32."""
    dt = _act_dtype(mode)
    if mode != "f32":
        lo = jnp.float8_e4m3fn if mode == "fp8" else jnp.bfloat16
        a = a.astype(lo).astype(jnp.float32)
        b = b.astype(lo).astype(jnp.float32)
    return jnp.einsum(spec, a, b,
                      preferred_element_type=jnp.float32).astype(dt)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms(x, w, arch):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    eps = arch.get("norm_eps", 1e-5)
    return (x32 / jnp.sqrt(var + eps) * w).astype(x.dtype)


def _rope(x, pos, arch):
    """x (B, S, h, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    half = hd // 2
    theta = arch.get("rope_theta", 10_000.0)
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _attention(p, x, arch, mode):
    B, S, _ = x.shape
    H, KV, hd = arch["n_heads"], arch["n_kv_heads"], head_dim(arch)
    q = _mm("bsd,dhk->bshk", x, p["wq"], mode)
    k = _mm("bsd,dhk->bshk", x, p["wk"], mode)
    v = _mm("bsd,dhk->bshk", x, p["wv"], mode)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, arch), _rope(k, pos, arch)
    q = q.reshape(B, S, KV, H // KV, hd)
    s = _mm("bqkgh,bskh->bkgqs", q, k, mode).astype(jnp.float32)
    s = s / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgqs,bskh->bqkgh", w, v, mode).reshape(B, S, H, hd)
    return _mm("bshk,hkd->bsd", o, p["wo"], mode)


def _swiglu(x, wg, wu, wd, mode, spec_in="bsd,df->bsf",
            spec_out="bsf,fd->bsd"):
    g = _mm(spec_in, x, wg, mode)
    u = _mm(spec_in, x, wu, mode)
    return _mm(spec_out, jax.nn.silu(g) * u, wd, mode)


def _moe(p, x, arch, mode):
    """Every token through its top-k experts, weighted by the softmax
    router's top-k probabilities renormalised to sum 1; plus the
    Switch load-balance term E * sum_e(mean prob_e * share routed_e)."""
    E, k = arch["n_experts"], arch["top_k"]
    logits = _mm("bsd,de->bse", x, p["w_router"], mode).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (B,S,k,E)
    combine = jnp.einsum("bsk,bske->bse", top, onehot)
    y = _swiglu(x, p["w_gate"], p["w_up"], p["w_down"], mode,
                "bsd,edf->bsef", "bsef,efd->bsed")
    out = jnp.einsum("bse,bsed->bsd", combine.astype(y.dtype), y,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    share = jnp.mean(jnp.sum(onehot, axis=2).reshape(-1, E), axis=0) / k
    return out, E * jnp.sum(me * share)


def hidden(params: dict, tokens, arch: dict, mode: str = "f32"):
    """tokens (B, S) -> final-normed hidden states (B, S, d), aux."""
    dt = _act_dtype(mode)
    x = params["embed"][tokens].astype(dt)

    def layer(x, p):
        x = x + _attention(p["attn"], _rms(x, p["norm1"], arch), arch, mode)
        h = _rms(x, p["norm2"], arch)
        if "moe" in p:
            y, aux = _moe(p["moe"], h, arch, mode)
        else:
            y = _swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                        p["mlp"]["w_down"], mode)
            aux = jnp.zeros((), jnp.float32)
        return x + y, aux

    x, aux = jax.lax.scan(layer, x, params["blocks"])
    return _rms(x, params["final_norm"], arch), jnp.sum(aux)


def _head(params, arch):
    V = arch["vocab_size"]
    head = (params["embed"].T if arch.get("tie_embeddings")
            else params["lm_head"])
    return head[:, :V]          # the published vocabulary only


def logits(params, tokens, arch, mode="f32"):
    x, _ = hidden(params, tokens, arch, mode)
    return _mm("bsd,dv->bsv", x, _head(params, arch), mode).astype(
        jnp.float32)


def loss(params, tokens, arch, mode="f32"):
    """Mean next-token cross entropy over positions 0..S-2, plus the
    router's load-balance term times AUX_WEIGHT."""
    x, aux = hidden(params, tokens, arch, mode)
    lg = _mm("bsd,dv->bsv", x[:, :-1], _head(params, arch),
             mode).astype(jnp.float32)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    ce = jnp.mean(jax.nn.logsumexp(lg, -1) - gold)
    return ce + AUX_WEIGHT * aux


# ---------------------------------------------------------------------------
# local SGD rounds (heavy-ball momentum, server averaging)
# ---------------------------------------------------------------------------


_LOCAL = {}


def make_local(arch: dict, mode: str, lr: float, beta: float, steps: int):
    """Jitted ``(params, mu, tokens) -> (params, mu, loss at the result)``:
    ``steps`` momentum steps of full-batch GD on one group's batch (one
    per configuration, mode and schedule in a process)."""
    key = (json.dumps(arch, sort_keys=True), mode, lr, beta, steps)
    if key not in _LOCAL:
        _LOCAL[key] = _make_local(arch, mode, lr, beta, steps)
    return _LOCAL[key]


def _make_local(arch, mode, lr, beta, steps):

    def local(p, mu, tokens):
        def body(carry, _):
            p, mu = carry
            g = jax.grad(loss)(p, tokens, arch, mode)
            mu = jax.tree.map(lambda m, gg: beta * m + gg.astype(
                jnp.float32), mu, g)
            p = jax.tree.map(lambda a, m: a - lr * m, p, mu)
            return (p, mu), None

        with _precision(mode):
            (p, mu), _ = jax.lax.scan(body, (p, mu), None, length=steps)
            end = loss(p, tokens, arch, mode)
        return p, mu, end

    return jax.jit(local)


def _flat(tree) -> dict:
    """``{"a": {"b": x}}`` -> ``{"a/b": x}``."""
    flat = {}

    def walk(t, pre):
        for k, v in t.items():
            if isinstance(v, dict):
                walk(v, pre + k + "/")
            else:
                flat[pre + k] = v
    walk(tree, "")
    return flat


def run_rounds(params: dict, batches, arch: dict, lr: float, beta: float,
               steps: int, mode: str = "f32", fault: str = ""):
    """The first ``len(batches)`` rounds of packed local SGD as the
    configuration states it: each of G groups takes ``steps`` momentum
    steps on its rows of the round's batch (G, B, S), then params and
    momentum are averaged over the groups. ``fault`` plants a fault in
    the reference put in the program's place: ``"half"`` trains each
    group on half its rows, ``"noexchange"`` skips the averaging.

    Returns the readings the training check compares: ``loss`` (rounds
    x G, at each round's result before the exchange), ``mu1`` (per-leaf
    norm of the first group's momentum after the first round: the
    gradients as the optimizer got them), ``dparams`` (per-leaf norm of
    the first group's parameter change after the last round)."""
    local = make_local(arch, mode, lr, beta, steps)
    p0 = params
    mu0 = _zeros(params)
    groups = [(p0, mu0)] * batches[0].shape[0]
    out = {"loss": []}
    for r, tokens in enumerate(batches):
        ends = []
        for g in range(len(groups)):
            # each group's old state is dropped as its new one is made,
            # so that G distinct states (no exchange) fit beside G new
            p, mu = groups[g]
            groups[g] = None
            rows = tokens[g]
            if fault == "half":
                rows = rows[: rows.shape[0] // 2]
            p, mu, end = local(p, mu, rows)
            ends.append(float(end))
            groups[g] = (p, mu)
            del p, mu
        out["loss"].append(ends)
        if fault != "noexchange":
            pm = _avg([p for p, _ in groups])
            mm = _avg([m for _, m in groups])
            groups = [(pm, mm)] * len(groups)
        if r == 0:
            out["mu1"] = _norms(groups[0][1])
    # the first group's state, as the program's first row is read
    out["dparams"] = _norms(jax.tree.map(lambda a, b: a - b, groups[0][0],
                                         p0))
    return out


@jax.jit
def _zeros(tree):
    return jax.tree.map(jnp.zeros_like, tree)


@jax.jit
def _avg(trees):
    return jax.tree.map(lambda *xs: sum(xs) / len(xs), *trees)


@jax.jit
def _norm_leaves(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


def _norms(tree) -> dict:
    flat = _flat(tree)
    n = _norm_leaves(flat)
    return {k: float(v) for k, v in n.items()}
