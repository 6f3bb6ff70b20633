"""The no-drop grouped expert layer, the loss over the published
vocabulary, and a packed Granite-shaped round against the plain
reference (``bench/refs/decoder.py``), all on the CPU at small sizes.

The grouped layer is held to ``densemask`` (every expert on every token,
gated): forward output, the router's aux loss and every gradient, under
uniform, skewed and empty-expert routing, alone and vmapped over groups
(where its grouped products fold the groups into the experts).
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, optim
from repro.configs.base import ArchConfig, get_config
from repro.core import localsgd as lsgd
from repro.models import api, build_model
from repro.models import moe as moem
from repro.models.layers import init_params
from repro.optim import packing

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
import common  # noqa: E402
from refs import decoder as ref  # noqa: E402

# a reduced Granite: every kind of part, widths cut for the CPU
CFG = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                          d_model=64, d_ff=32, n_experts=8, top_k=4)
# routing: a bias on each expert's router logit, through a constant
# first feature of x (uniform: none; skewed: most pairs to experts 0 and
# 1; empty: experts 5-7 get no pair)
BIAS = {"uniform": np.zeros(8),
        "skewed": np.array([3.0, 3.0, 0, 0, 0, 0, 0, 0]),
        "empty": np.array([0, 0, 0, 0, 0, -30.0, -30.0, -30.0])}


def _routed(routing, key, shape):
    p = init_params(moem.moe_defs(CFG), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), shape + (CFG.d_model,))
    x = x.at[..., 0].set(1.0)
    p["w_router"] = p["w_router"].at[0].set(jnp.asarray(BIAS[routing],
                                                        jnp.float32))
    return p, x


def _layer(impl):
    cfg = dataclasses.replace(CFG, moe_impl=impl)

    def f(p, x):
        y, aux, load = moem.moe_forward(p, x, cfg)
        # a loss that weighs every output element differently
        w = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
        return jnp.sum(w * y) + aux, (y, aux, load)

    return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)


def _assert_close(a, b):
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(u, v, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("routing", ["uniform", "skewed", "empty"])
def test_grouped_matches_densemask(routing, key):
    p, x = _routed(routing, key, (2, 16))
    (_, (y, aux, load)), g = jax.jit(_layer("grouped"))(p, x)
    (_, (y0, aux0, load0)), g0 = jax.jit(_layer("densemask"))(p, x)
    _assert_close((y, aux, load, g), (y0, aux0, load0, g0))
    _, idx, _ = moem.router(p, x, CFG)
    sizes = np.bincount(np.asarray(idx).ravel(), minlength=CFG.n_experts)
    assert sizes.sum() == 2 * 16 * CFG.top_k        # every pair computed
    if routing == "skewed":
        assert sizes[:2].sum() > 0.4 * sizes.sum()
    if routing == "empty":
        assert (sizes[5:] == 0).all() and (sizes[:5] > 0).all()
    np.testing.assert_allclose(
        load, sizes.max() / sizes.mean(), rtol=1e-6)


def test_grouped_matches_densemask_vmapped(key):
    """Two groups routed differently (one with empty experts) through one
    vmapped call: the grouped products fold the groups into the experts."""
    p0, x0 = _routed("uniform", key, (2, 8))
    p1, x1 = _routed("empty", jax.random.fold_in(key, 7), (2, 8))
    p = jax.tree.map(lambda a, b: jnp.stack([a, b]), p0, p1)
    x = jnp.stack([x0, x1])
    out = jax.jit(jax.vmap(_layer("grouped")))(p, x)
    want = jax.jit(jax.vmap(_layer("densemask")))(p, x)
    _assert_close(out, want)


def test_grouped_matmul_runs_and_grads():
    """Uneven runs, one empty, against a per-row loop."""
    sizes = jnp.asarray([3, 0, 5, 4], jnp.int32)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k1, (12, 16))
    w = jax.random.normal(k2, (4, 16, 8))
    dy = jax.random.normal(k3, (12, 8))
    expert = np.repeat(np.arange(4), np.asarray(sizes))

    def plain(x, w):
        return jnp.einsum("mk,mkn->mn", x, w[expert])

    y, vjp = jax.vjp(lambda x, w: moem.grouped_matmul(x, w, sizes), x, w)
    y0, vjp0 = jax.vjp(plain, x, w)
    _assert_close((y, vjp(dy)), (y0, vjp0(dy)))


# ---------------------------------------------------------------------------
# the loss over the published vocabulary
# ---------------------------------------------------------------------------

V, V_PAD = 1000, 1024


def _ce_inputs():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k1, (2, 32, 16))
    head = jax.random.normal(k2, (16, V_PAD))
    labels = jax.random.randint(k3, (2, 32), 0, V)
    return x, head, labels


def test_chunked_ce_ignores_padding_rows():
    x, head, labels = _ce_inputs()
    got = api._chunked_ce(x, head, labels, chunk=8, vocab_size=V)
    lg = jnp.einsum("bsd,dv->bsv", x, head[:, :V])
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    want = jnp.mean(jax.nn.logsumexp(lg, -1) - gold)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # whatever the padding rows hold, the loss is the same
    for fill in (1e4, -1e4, jnp.nan):
        other = head.at[:, V:].set(fill)
        assert float(api._chunked_ce(x, other, labels, chunk=8,
                                     vocab_size=V)) == float(got)


def test_logits_mask_padding_rows():
    arch = _arch(family="dense")
    model = build_model(ArchConfig(**arch), schedule="rect")
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((1, 4), jnp.int32)
    lg = model.logits(params, {"tokens": toks})
    assert lg.shape[-1] == V_PAD
    assert bool(jnp.all(lg[..., V:] == jnp.finfo(jnp.float32).min))
    assert bool(jnp.all(jnp.argmax(lg, -1) < V))


def test_mask_padding_is_no_op_without_padding():
    x = jnp.ones((2, 256))
    assert api.mask_padding(x, 256) is x


def _arch(family="moe", **kw):
    a = {"name": "tiny-granite", "family": family, "source": "test",
         "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "d_ff": 32, "vocab_size": V, "mlp_type": "swiglu",
         "tie_embeddings": True, "dtype": "float32",
         "param_dtype": "float32"}
    if family == "moe":
        a.update(n_experts=8, top_k=2)
    a.update(kw)
    return a


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_model_loss_matches_reference_over_published_vocab(family):
    arch = _arch(family)
    model = build_model(ArchConfig(**arch), schedule="rect")
    params = common.nest(common.make_weights(ref.param_specs(arch), 11))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, V)
    got = model.loss(params, {"tokens": toks})
    with jax.default_matmul_precision("highest"):
        want = ref.loss(params, toks, arch)
    np.testing.assert_allclose(got, want, rtol=2e-5)


# ---------------------------------------------------------------------------
# one packed round against the reference
# ---------------------------------------------------------------------------


def test_packed_round_matches_reference():
    """A 2-layer Granite-shaped round (G=2, T=2, momentum, server fp32
    averaging of params and momentum) against ``refs.decoder.run_rounds``:
    the losses at the result, and the per-leaf norms of the momentum and
    of the parameters' change."""
    arch = _arch()
    G, T, B, S, lr, beta = 2, 2, 2, 16, 0.05, 0.9
    model = build_model(ArchConfig(**arch), schedule="rect")
    flat = common.make_weights(ref.param_specs(arch), 21)
    params = common.nest(flat)
    layout = packing.layout_of(params)
    opt = optim.get("momentum", lr, packed=True, beta=beta)
    exch = comm.get_exchange("server", "fp32", G)
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=T, metrics="final",
                               average_opt_state=True)
    rnd = jax.jit(lsgd.make_local_round(model.loss_and_stats, opt, lcfg,
                                        layout=layout, exchange=exch,
                                        loss_has_aux=True))
    state = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                            exchange=exch, average_opt_state=True)
    toks = jax.random.randint(jax.random.PRNGKey(2), (G, B, S), 0, V)
    state, m = rnd(state, {"tokens": toks})
    assert m["expert_load"].shape == (G, arch["n_layers"])
    assert bool(jnp.all(m["expert_load"] >= 1.0))
    with jax.default_matmul_precision("highest"):
        want = ref.run_rounds(params, [toks], arch, lr, beta, T)
    np.testing.assert_allclose(m["loss"], want["loss"][0], rtol=1e-4)
    mu = common.flatten(packing.unpack(state["opt"]["mu"][0], layout))
    new = common.flatten(packing.unpack(state["params"][0], layout))
    for k, v in want["mu1"].items():
        np.testing.assert_allclose(float(jnp.linalg.norm(mu[k])), v,
                                   rtol=1e-3, err_msg=k)
    for k, v in want["dparams"].items():
        d = float(jnp.linalg.norm(new[k] - flat[k]))
        np.testing.assert_allclose(d, v, rtol=1e-3, atol=1e-7, err_msg=k)


# ---------------------------------------------------------------------------
# the layer's scopes in the compiled round, and the readers of them
# ---------------------------------------------------------------------------

MOE = ("local_steps", "fwd_bwd", "moe")


def test_round_hlo_carries_the_moe_scopes():
    import re

    import trace_scopes as ts
    arch = _arch()
    model = build_model(ArchConfig(**arch), schedule="rect")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layout = packing.layout_of(params)
    G = 2
    opt = optim.get("momentum", 0.05, packed=True, beta=0.9)
    exch = comm.get_exchange("server", "fp32", G)
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2, metrics="final")
    rnd = lsgd.make_local_round(model.loss, opt, lcfg, layout=layout,
                                exchange=exch)
    state = jax.eval_shape(lambda p: lsgd.init_state(
        p, opt, n_groups=G, layout=layout, exchange=exch), params)
    batch = {"tokens": jax.ShapeDtypeStruct((G, 2, 16), jnp.int32)}
    hlo = jax.jit(rnd).lower(state, batch).compile().as_text()
    paths = {ts.scope_path(n) for n in re.findall(r'op_name="([^"]*)"', hlo)}
    for part in ("router", "permute", "experts"):
        assert any(ts.holds(p, MOE + (part,)) for p in paths), part
    # the result's evaluation runs the layer outside the local steps
    assert any(ts.holds(p, ("final_eval", "moe", "experts")) for p in paths)


def _read(name, ctx):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _ctx(scopes, arch):
    return {"kind": "train", "rounds": 5, "arch": arch,
            "groups_per_chip": 2,
            "traffic": {"local_steps": 4, "per_group_batch": 2,
                        "seq_len": 1024},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "reduced": {"scopes": scopes}}


def test_moe_readers_on_hand_made_scopes():
    import json

    import moe_counts
    arch = json.loads((BENCH / "configs" / "granite-moe-1b-a400m-l4.json")
                      .read_text())["arch"]
    step = "jit(round_)/local_steps/fwd_bwd/moe"
    scopes = {f"{step}/router": 0.02, f"{step}/permute": 0.06,
              f"{step}/experts": 0.40, step: 0.01,
              "jit(round_)/final_eval/moe/experts": 0.5,
              "jit(round_)/local_steps/fwd_bwd": 1.0}
    ctx = _ctx(scopes, arch)
    steps = 5 * 4
    assert _read("moe_ms.train", ctx) == pytest.approx(
        1000 * 0.49 / steps)
    assert _read("moe_route_ms.train", ctx) == pytest.approx(
        1000 * 0.08 / steps)
    # 18 d f FLOPs a routed pair: 2 groups x 2,048 tokens x top-8 x 4
    # layers x 18 x 1024 x 512 = 1.237 TFLOP a step, bound by the FLOPs
    flops = moe_counts.grouped_mm_flops(arch, 2048)
    assert flops == 18 * 1024 * 512 * 2048 * 8 * 4
    assert moe_counts.grouped_mm_bytes(arch, 2048) / 819e9 < flops / 197e12
    need = 2 * flops / 197e12
    assert _read("expert_mm_roofline.train", ctx) == pytest.approx(
        100 * need / (0.40 / steps))
    # a model without experts, or no such scope, reads nothing
    dense = dict(arch, n_experts=0)
    assert _read("expert_mm_roofline.train", _ctx(scopes, dense)) is None
    bare = {"jit(round_)/local_steps/fwd_bwd": 1.0}
    for name in ("moe_ms.train", "moe_route_ms.train",
                 "expert_mm_roofline.train"):
        assert _read(name, _ctx(bare, arch)) is None
