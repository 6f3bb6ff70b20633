"""Property tests for model numerics (hypothesis where randomized)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs.base import get_config
from repro.models import attention as attn
from repro.models import moe as moem
from repro.models.api import _chunked_ce, _embed_lookup
from repro.models.layers import apply_rope, rms_norm, softmax_cross_entropy


# ---------------------------------------------------------------------------
# Blocked causal attention == full attention (both schedules)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["tri", "rect"])
@pytest.mark.parametrize("B,S,H,KV,hd,block", [
    (1, 8, 2, 2, 4, 2), (2, 16, 4, 2, 8, 4), (1, 32, 2, 1, 16, 8),
])
def test_blocked_equals_full(schedule, B, S, H, KV, hd, block, key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    mask = jnp.tril(jnp.ones((S, S), bool))
    want = attn.full_attention(q, k, v, mask)
    got = attn.blocked_causal_attention(q, k, v, block, schedule)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("schedule", ["tri", "rect"])
def test_blocked_grads_equal_full(schedule, key):
    """The running max takes no gradient: q, k and v get full attention's."""
    B, S, H, KV, hd, block = 2, 16, 4, 2, 8, 4
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    w = jax.random.normal(ks[3], (B, S, H, hd))
    mask = jnp.tril(jnp.ones((S, S), bool))

    def f(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))
    want = f(lambda q, k, v: attn.full_attention(q, k, v, mask))(q, k, v)
    got = f(lambda q, k, v: attn.blocked_causal_attention(
        q, k, v, block, schedule))(q, k, v)
    for g, h in zip(got, want):
        np.testing.assert_allclose(g, h, atol=1e-5, rtol=1e-4)


def test_blocked_causality(key):
    B, S, H, hd, block = 1, 16, 2, 8, 4
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    o1 = attn.blocked_causal_attention(q, k, v, block, "tri")
    k2 = k.at[:, -1].add(50.0)
    o2 = attn.blocked_causal_attention(q, k2, v, block, "tri")
    np.testing.assert_allclose(o1[:, :-1], o2[:, :-1], atol=1e-6)


# ---------------------------------------------------------------------------
# Chunked CE == full CE; chunked embed == table lookup
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), b=st.integers(1, 3),
       s=st.sampled_from([4, 8, 16]), v=st.sampled_from([11, 32]))
def test_chunked_ce_matches_full(seed, b, s, v):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    d = 12
    x = jax.random.normal(ks[0], (b, s, d))
    w = jax.random.normal(ks[1], (d, v))
    labels = jax.random.randint(ks[2], (b, s), 0, v)
    mask = (jax.random.uniform(ks[2], (b, s)) > 0.3)
    got = _chunked_ce(x, w, labels, mask, chunk=4)
    want = softmax_cross_entropy(x @ w, labels, mask)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_embed_lookup_matches_take(key):
    V, D, B, S = 50, 16, 2, 12
    table = jax.random.normal(key, (V, D))
    toks = jax.random.randint(key, (B, S), 0, V)
    got = _embed_lookup(table, toks, jnp.float32)
    want = table[toks]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# RoPE / RMSNorm
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_rope_preserves_norm(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, 6, 2, 8))
    pos = jnp.arange(6)[None]
    y = apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1),
        rtol=1e-5)


def test_rope_relative_property(key):
    """<rope(q,i), rope(k,j)> depends only on i - j."""
    q = jax.random.normal(key, (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))

    def score(i, j):
        qi = apply_rope(q, jnp.array([[i]]), 10_000.0)
        kj = apply_rope(k, jnp.array([[j]]), 10_000.0)
        return float(jnp.sum(qi * kj))

    assert abs(score(3, 1) - score(10, 8)) < 1e-4
    assert abs(score(5, 5) - score(0, 0)) < 1e-4


def test_rms_norm_unit_variance(key):
    x = jax.random.normal(key, (4, 64)) * 7.0
    w = jnp.ones((64,))
    y = rms_norm(x, w)
    rms = jnp.sqrt(jnp.mean(y ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)


# ---------------------------------------------------------------------------
# MoE: the grouped (no-drop) layer == densemask
# ---------------------------------------------------------------------------


def test_moe_dispatch_matches_densemask(key):
    """The grouped layer, which dispatches every routed pair to its expert
    (no capacity, nothing dropped), equals the dense-masked oracle."""
    cfg = get_config("granite-moe-1b-a400m").reduced()
    defs = moem.moe_defs(cfg)
    from repro.models.layers import init_params
    p = init_params(defs, key)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
    y_dense, aux_d, load_d = moem.moe_densemask(p, x, cfg)
    y_grp, aux_g, load_g = moem.moe_grouped(p, x, cfg)
    np.testing.assert_allclose(y_grp, y_dense, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(aux_d, aux_g, rtol=1e-5)
    np.testing.assert_allclose(load_d, load_g)


def test_moe_decode_matches_forward(key):
    """Single-token decode path == full forward at S=1."""
    cfg = get_config("granite-moe-1b-a400m").reduced()
    defs = moem.moe_defs(cfg)
    from repro.models.layers import init_params
    p = init_params(defs, key)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 1, cfg.d_model))
    y_fwd, _, _ = moem.moe_densemask(p, x, cfg)
    y_dec, _ = moem.moe_decode(p, x, cfg)
    np.testing.assert_allclose(y_dec, y_fwd, atol=1e-4, rtol=1e-4)


def test_moe_router_gates_normalized(key):
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    defs = moem.moe_defs(cfg)
    from repro.models.layers import init_params
    p = init_params(defs, key)
    x = jax.random.normal(key, (2, 4, cfg.d_model))
    gates, idx, aux = moem.router(p, x, cfg)
    np.testing.assert_allclose(jnp.sum(gates, -1), 1.0, atol=1e-5)
    assert gates.shape[-1] == cfg.top_k
    assert bool(jnp.all(idx >= 0)) and bool(jnp.all(idx < cfg.n_experts))
    assert float(aux) >= 1.0 - 1e-3  # Switch aux >= 1 at balance
