"""Per-architecture smoke tests: reduced variants (2 layers, d_model<=512,
<=4 experts) run one forward / train grad step / decode step on CPU with
shape and finiteness assertions."""
import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.configs.base import ARCH_IDS, get_config
from repro.models import build_model


def make_batch(cfg, key, B=2, S=32):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model))
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.n_frames, cfg.d_model))
    return batch


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    cfg = get_config(request.param).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_forward_shapes(arch_setup, key):
    cfg, model, params = arch_setup
    batch = make_batch(cfg, key)
    x, aux, _ = model.forward(params, batch)
    assert x.shape == (2, 32, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


def test_loss_and_grad_step(arch_setup, key):
    cfg, model, params = arch_setup
    batch = make_batch(cfg, key)
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert bool(jnp.isfinite(loss)) and loss > 0
    gnorm = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    assert bool(jnp.isfinite(gnorm)) and gnorm > 0
    opt = optim.sgd(1e-2)
    new_params, _ = opt.step(params, grads, opt.init(params))
    loss2 = model.loss(new_params, batch)
    assert bool(jnp.isfinite(loss2))
    assert loss2 < loss  # one full-batch GD step must descend


def test_decode_step(arch_setup, key):
    cfg, model, params = arch_setup
    B, W = 2, 64
    cache = model.init_cache(B, W)
    tok = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    logits, new_cache = model.decode_step(
        params, cache, tok, jnp.asarray(0, jnp.int32))
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # cache structure preserved
    assert jax.tree.structure(new_cache) == jax.tree.structure(
        {k: v for k, v in cache.items()})


def test_decode_matches_prefill_next_token(arch_setup, key):
    """Greedy next-token from decode-with-cache == from a fresh forward.

    Run S tokens through decode one at a time, compare the final-position
    logits against model.logits on the same prefix."""
    cfg, model, params = arch_setup
    if cfg.family in ("vlm", "audio"):
        pytest.skip("prefix modalities differ between paths")
    B, S = 1, 8
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    full = model.logits(params, {"tokens": toks})     # (B,S,V)

    cache = model.init_cache(B, S)
    logits = None
    for t in range(S):
        logits, cache = model.decode_step(
            params, cache, toks[:, t:t + 1], jnp.asarray(t, jnp.int32))
    # compare distributions at the last position
    a = jax.nn.log_softmax(full[:, -1].astype(jnp.float32))
    b = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32))
    # reduced configs run bf16-free (dtype float32) so this is tight-ish
    assert jnp.max(jnp.abs(a - b)) < 5e-2, float(jnp.max(jnp.abs(a - b)))


def test_sliding_window_decode(arch_setup, key):
    """Ring-buffer cache accepts positions beyond the window."""
    cfg, model, params = arch_setup
    B, W = 1, 16
    cache = model.init_cache(B, W)
    tok = jnp.zeros((B, 1), jnp.int32)
    for pos in [0, 1, W - 1, W, W + 3]:
        logits, cache = model.decode_step(
            params, cache, tok, jnp.asarray(pos, jnp.int32))
        assert bool(jnp.all(jnp.isfinite(logits)))


def test_batched_prefill_matches_stepwise(arch_setup, key):
    """Dense/MoE families: one batched prefill == token-by-token decode
    (same cache contents -> identical next-token logits)."""
    cfg, model, params = arch_setup
    if not hasattr(model, "prefill") or cfg.family in ("vlm", "audio"):
        pytest.skip("prefill path is dense/moe only")
    B, S, W = 1, 6, 12
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)

    logits_pf, cache_pf = model.prefill(params, {"tokens": toks}, W)

    cache = model.init_cache(B, W)
    logits_st = None
    for t in range(S):
        logits_st, cache = model.decode_step(
            params, cache, toks[:, t:t + 1], jnp.asarray(t, jnp.int32))
    a = jax.nn.log_softmax(logits_pf[:, 0].astype(jnp.float32))
    b = jax.nn.log_softmax(logits_st[:, 0].astype(jnp.float32))
    assert float(jnp.max(jnp.abs(a - b))) < 5e-2
    # continuing decode from the prefilled cache agrees too
    nxt = jnp.argmax(logits_pf[:, :, :cfg.vocab_size], -1).astype(jnp.int32)
    l1, _ = model.decode_step(params, cache_pf, nxt,
                              jnp.asarray(S, jnp.int32))
    l2, _ = model.decode_step(params, cache, nxt,
                              jnp.asarray(S, jnp.int32))
    assert float(jnp.max(jnp.abs(
        jax.nn.log_softmax(l1[:, 0].astype(jnp.float32))
        - jax.nn.log_softmax(l2[:, 0].astype(jnp.float32))))) < 5e-2
