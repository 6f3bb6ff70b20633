"""Flat-buffer engine (optim.packing + packed optimizers + packed rounds).

Acceptance-critical invariants:
  * pack/unpack roundtrip preserves shapes, dtypes, and values,
  * packed fused rounds == per-leaf pytree rounds for sgd / momentum /
    adamw over a full multi-round run, with average_opt_state on AND off
    (params and opt state within 1e-5),
  * the same parity holds on a real transformer loss,
  * metric contract: "traj" matches the pytree round's metrics exactly;
    "final" evaluates at the round's result,
  * modes not on the fast path raise instead of silently degrading.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import localsgd as lsgd
from repro.optim import packing


def quad_loss(params, batch):
    r = batch["A"] @ params["w"] - batch["b"]
    return 0.5 * jnp.sum(r ** 2) + 0.1 * jnp.sum(params["u"] ** 2)


def make_problem(key, G=3, r=4, d=6):
    ks = jax.random.split(key, 4)
    A = jax.random.normal(ks[0], (G, r, d)) / np.sqrt(d)
    w_star = jax.random.normal(ks[1], (d,))
    batch = {"A": A, "b": jnp.einsum("grd,d->gr", A, w_star)}
    params = {"w": jax.random.normal(ks[2], (d,)),
              "u": jax.random.normal(ks[3], (2, 3))}
    return params, batch


def _flat_only(monkeypatch):
    """Build the next rounds on the per-step flat path whatever the rule
    says (where the fused kernels run, and the reference the
    leaf-carrying round must match)."""
    monkeypatch.setattr(lsgd, "carries_leaves", lambda *a, **k: False)


# ---------------------------------------------------------------------------
# layout / pack / unpack
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip(key):
    ks = jax.random.split(key, 3)
    tree = {"a": jax.random.normal(ks[0], (3, 4)),
            "b": {"c": jax.random.normal(ks[1], (5,)).astype(jnp.bfloat16),
                  "d": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
            "e": jnp.float32(2.5)}
    layout = packing.layout_of(tree)
    buf = packing.pack(tree, layout)
    assert buf.shape == (layout.size,) and buf.dtype == jnp.float32
    assert layout.size == 12 + 5 + 6 + 1
    back = packing.unpack(buf, layout)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_pack_unpack_group_axis(key):
    G = 4
    tree = {"a": jax.random.normal(key, (3, 4)), "b": jnp.ones((5,))}
    layout = packing.layout_of(tree)
    tree_G = lsgd.replicate(tree, G)
    buf_G = packing.pack(tree_G, layout)
    assert buf_G.shape == (G, layout.size)
    back = packing.unpack(buf_G, layout)
    for a, b in zip(jax.tree.leaves(tree_G), jax.tree.leaves(back)):
        np.testing.assert_allclose(a, b)


def test_layout_abstract_matches_pack(key):
    tree = {"a": jnp.ones((3, 4)), "b": jnp.ones((5,))}
    layout = packing.layout_of(tree)
    abs_ = layout.abstract((2,))
    assert abs_.shape == (2, layout.size) and abs_.dtype == jnp.float32


def test_value_and_flat_grad_matches_tree_grad(key):
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    b0 = {"A": batch["A"][0], "b": batch["b"][0]}
    loss_t, g_tree = jax.value_and_grad(quad_loss)(params, b0)
    loss_f, g_flat = packing.value_and_flat_grad(quad_loss, layout)(
        packing.pack(params, layout), b0)
    np.testing.assert_allclose(loss_f, loss_t, rtol=1e-6)
    np.testing.assert_allclose(g_flat, packing.pack(g_tree, layout),
                               rtol=1e-6, atol=1e-7)


def test_average_groups_flat_matches_per_leaf(key):
    params, _ = make_problem(key)
    layout = packing.layout_of(params)
    G = 3
    tree_G = jax.tree.map(
        lambda x: x[None] * jnp.arange(1., G + 1).reshape((G,) + (1,) * x.ndim),
        params)
    per_leaf = lsgd.average_groups(tree_G)
    flat = lsgd.average_groups(packing.pack(tree_G, layout))
    np.testing.assert_allclose(flat, packing.pack(per_leaf, layout),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# packed round == per-leaf pytree round (the acceptance parity)
# ---------------------------------------------------------------------------


MOMENT_KEYS = {"sgd": [], "momentum": ["mu"], "adamw": ["m", "v"]}


@pytest.mark.parametrize("name", ["sgd", "momentum", "adamw"])
@pytest.mark.parametrize("avg_opt", [True, False])
def test_packed_round_parity(name, avg_opt, key):
    """Full multi-round run: params AND opt state agree within 1e-5."""
    params, batch = make_problem(key)
    G = 3
    layout = packing.layout_of(params)
    opt_t = optim.get(name, 0.05)
    opt_p = optim.get(name, 0.05, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4,
                              average_opt_state=avg_opt, metrics="traj")
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    rnd_p = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg,
                                          layout=layout))
    st = lsgd.init_state(params, opt_t, n_groups=G)
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    for _ in range(3):
        st, mt = rnd_t(st, batch)
        sp, mp = rnd_p(sp, batch)

    wt = lsgd.server_params(st)
    wp = lsgd.server_params(sp, layout=layout)
    for a, b in zip(jax.tree.leaves(wt), jax.tree.leaves(wp)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)
    # opt-state parity: packed moment buffers == packed per-leaf moments
    for mk in MOMENT_KEYS[name]:
        for g in range(G):
            ref = packing.pack(
                jax.tree.map(lambda x: x[g], st["opt"][mk]), layout)
            np.testing.assert_allclose(sp["opt"][mk][g], ref,
                                       rtol=1e-5, atol=1e-6)
    # metric parity in traj mode
    np.testing.assert_allclose(mp["loss"], mt["loss"], rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(mp["grad_sq_traj"], mt["grad_sq_traj"],
                               rtol=1e-4, atol=1e-8)


@pytest.mark.parametrize("name", ["sgd", "momentum", "adamw"])
def test_packed_round_parity_pallas_kernels(name, key, monkeypatch):
    """Same parity through the fused Pallas kernels (interpret on CPU),
    which run on the flat path (a round that carries its leaves updates
    them with the jnp formula)."""
    _flat_only(monkeypatch)
    params, batch = make_problem(key)
    G = 2
    layout = packing.layout_of(params)
    opt_t = optim.get(name, 0.05)
    opt_p = optim.get(name, 0.05, packed=True, impl="pallas")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=3)
    batch2 = {"A": batch["A"][:G], "b": batch["b"][:G]}
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    rnd_p = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg,
                                          layout=layout))
    st = lsgd.init_state(params, opt_t, n_groups=G)
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    st, _ = rnd_t(st, batch2)
    sp, _ = rnd_p(sp, batch2)
    for a, b in zip(jax.tree.leaves(lsgd.server_params(st)),
                    jax.tree.leaves(lsgd.server_params(sp, layout=layout))):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


def test_packed_round_parity_real_model(key):
    """Parity holds on an actual transformer loss (reduced paper-mlp)."""
    from repro.configs.base import get_config
    from repro.models import build_model

    cfg = get_config("paper-mlp").reduced()
    model = build_model(cfg, schedule="rect")
    params = model.init(jax.random.PRNGKey(0))
    layout = packing.layout_of(params)
    G = 2
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (G, 1, 16)), jnp.int32)}
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2, metrics="traj")
    opt_t, opt_p = optim.sgd(0.05), optim.packed("sgd", 0.05, impl="jnp")
    rnd_t = jax.jit(lsgd.make_local_round(model.loss, opt_t, lcfg))
    rnd_p = jax.jit(lsgd.make_local_round(model.loss, opt_p, lcfg,
                                          layout=layout))
    st = lsgd.init_state(params, opt_t, n_groups=G)
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    st, mt = rnd_t(st, batch)
    sp, mp = rnd_p(sp, batch)
    np.testing.assert_allclose(mp["loss"], mt["loss"], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(lsgd.server_params(st)),
                    jax.tree.leaves(lsgd.server_params(sp, layout=layout))):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


def test_packed_t_i_parity(key):
    params, batch = make_problem(key)
    G = 3
    layout = packing.layout_of(params)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=8, t_i=(1, 4, 8))
    opt_t, opt_p = optim.sgd(0.05), optim.packed("sgd", 0.05, impl="jnp")
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    rnd_p = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg,
                                          layout=layout))
    st = lsgd.init_state(params, opt_t, n_groups=G)
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    st, mt = rnd_t(st, batch)
    sp, mp = rnd_p(sp, batch)
    assert list(np.asarray(mp["inner_steps"])) == [1, 4, 8]
    for a, b in zip(jax.tree.leaves(lsgd.server_params(st)),
                    jax.tree.leaves(lsgd.server_params(sp, layout=layout))):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_packed_t_i_adamw_parity(impl, key, monkeypatch):
    """The PR-1 leftover, lifted (DESIGN.md §10): per-node t_i with a
    count-dependent update runs the fused step vmapped over G with a
    PER-GROUP count vector. Multi-round parity vs the pytree path for
    params, moments, AND the per-group counters (count_g = r * t_i[g]).
    With "pallas" the round is held on the flat path, where the fused
    kernel runs (T=8 would carry the leaves)."""
    if impl == "pallas":
        _flat_only(monkeypatch)
    params, batch = make_problem(key)
    G = 3
    layout = packing.layout_of(params)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=8, t_i=(1, 4, 8))
    opt_t = optim.adamw(0.01)
    opt_p = optim.packed("adamw", 0.01, impl=impl)
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    rnd_p = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg,
                                          layout=layout))
    st = lsgd.init_state(params, opt_t, n_groups=G)
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    for _ in range(2):
        st, mt = rnd_t(st, batch)
        sp, mp = rnd_p(sp, batch)
    assert list(np.asarray(mp["inner_steps"])) == [1, 4, 8]
    # per-group counters stopped at t_i, matching the pytree masking
    np.testing.assert_array_equal(np.asarray(sp["opt"]["count"]),
                                  np.asarray(st["opt"]["count"]))
    np.testing.assert_array_equal(np.asarray(sp["opt"]["count"]),
                                  np.asarray([2, 8, 16], np.int32))
    for a, b in zip(jax.tree.leaves(lsgd.server_params(st)),
                    jax.tree.leaves(lsgd.server_params(sp, layout=layout))):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)
    for mk in ("m", "v"):
        for g in range(G):
            ref = packing.pack(
                jax.tree.map(lambda x: x[g], st["opt"][mk]), layout)
            np.testing.assert_allclose(sp["opt"][mk][g], ref,
                                       rtol=1e-5, atol=1e-7)


def test_packed_t_i_schedule_parity(key):
    """lr schedules are count-dependent too: under t_i they take the same
    vmapped per-group-count path and match the pytree round."""
    params, batch = make_problem(key)
    G = 2
    layout = packing.layout_of(params)
    lr_fn = optim.cosine_schedule(0.1, warmup=2, total=20)
    opt_t = optim.with_schedule(optim.sgd, lr_fn)
    opt_p = optim.with_schedule(
        lambda lr: optim.packed("sgd", lr, impl="jnp"), lr_fn)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4, t_i=(1, 4))
    batch2 = {"A": batch["A"][:G], "b": batch["b"][:G]}
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    rnd_p = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg,
                                          layout=layout))
    st = lsgd.init_state(params, opt_t, n_groups=G)
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    for _ in range(2):
        st, _ = rnd_t(st, batch2)
        sp, _ = rnd_p(sp, batch2)
    np.testing.assert_array_equal(np.asarray(sp["opt"]["count"]),
                                  np.asarray(st["opt"]["count"]))
    for a, b in zip(jax.tree.leaves(lsgd.server_params(st)),
                    jax.tree.leaves(lsgd.server_params(sp, layout=layout))):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


def test_packed_sync_step_parity(key):
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    b0 = {"A": batch["A"][0], "b": batch["b"][0]}
    opt_t, opt_p = optim.adamw(0.01), optim.packed("adamw", 0.01,
                                                   impl="jnp")
    st = lsgd.init_state(params, opt_t)
    sp = lsgd.init_state(params, opt_p, layout=layout)
    step_t = jax.jit(lsgd.make_sync_step(quad_loss, opt_t))
    step_p = jax.jit(lsgd.make_sync_step(quad_loss, opt_p, layout=layout))
    for _ in range(3):
        st, mt = step_t(st, b0)
        sp, mp = step_p(sp, b0)
    np.testing.assert_allclose(mp["grad_sq"], mt["grad_sq"], rtol=1e-4)
    ref = packing.pack(st["params"], layout)
    np.testing.assert_allclose(sp["params"], ref, rtol=1e-5, atol=1e-6)


def test_final_metrics_contract(key):
    """metrics="final" (default) reports loss/||grad||^2 at the round's
    RESULT — i.e. the grad_sq one update later than traj's last entry."""
    params, batch = make_problem(key)
    G = 3
    layout = packing.layout_of(params)
    opt_p = optim.packed("sgd", 0.05, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4)   # default final
    assert cfg.metrics == "final"
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg,
                                        layout=layout))
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    new_sp, m = rnd(sp, batch)
    m = {**m, **rnd.wire_bytes(sp)}
    from repro import obs
    assert set(m) == set(obs.round_metric_keys(("params",)))
    # per-stream split sums to the old total (sgd: params only)
    assert int(m["wire_bytes/params"]) == int(m["wire_bytes"])
    # the traj round reports the gradient made AT step T-1; final mode is
    # one descent update later, so on this convex problem it must be <=
    cfg_traj = dataclasses.replace(cfg, metrics="traj")
    rnd_traj = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg_traj,
                                             layout=layout))
    _, m_traj = rnd_traj(jax.tree.map(jnp.copy, sp), batch)
    # final-mode grad_sq must be <= traj's last recorded grad_sq for this
    # convex descent problem (one more update happened)
    assert np.all(np.asarray(m["grad_sq"])
                  <= np.asarray(m_traj["grad_sq"]) + 1e-8)


def test_packed_survives_schedule_and_clip_wrappers(key):
    """with_schedule/clip_by_global_norm must keep the packed/impl flags
    so the wrapped optimizer still routes to the flat-buffer path."""
    params, batch = make_problem(key)
    G = 2
    layout = packing.layout_of(params)
    # max_norm small enough to BIND: per-group clipping must also agree
    lr_fn = optim.cosine_schedule(0.05, warmup=2, total=20)
    opt_p = optim.clip_by_global_norm(
        optim.with_schedule(lambda lr: optim.packed("sgd", lr, impl="jnp"),
                            lr_fn), max_norm=0.5)
    opt_t = optim.clip_by_global_norm(
        optim.with_schedule(optim.sgd, lr_fn), max_norm=0.5)
    assert opt_p.packed and not opt_t.packed
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=3)
    batch2 = {"A": batch["A"][:G], "b": batch["b"][:G]}
    rnd_p = jax.jit(lsgd.make_local_round(quad_loss, opt_p, cfg,
                                          layout=layout))
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    sp = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    st = lsgd.init_state(params, opt_t, n_groups=G)
    sp, _ = rnd_p(sp, batch2)
    st, _ = rnd_t(st, batch2)
    for a, b in zip(jax.tree.leaves(lsgd.server_params(st)),
                    jax.tree.leaves(lsgd.server_params(sp, layout=layout))):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# where the packed round crosses between the buffers and the leaves
# ---------------------------------------------------------------------------


# (optimizer, T, t_i, inner_mode, metrics); the rule picks leaves in all
LEAF_CASES = [
    ("sgd", 4, None, "fixed_batch", "final"),
    ("momentum", 4, None, "fixed_batch", "final"),
    ("momentum", 4, None, "fixed_batch", "traj"),
    ("adamw", 3, None, "fixed_batch", "final"),
    ("adamw", 3, None, "fixed_batch", "traj"),
    ("momentum", 4, (1, 3, 4), "fixed_batch", "final"),  # shared count
    ("adamw", 4, (1, 3, 4), "fixed_batch", "traj"),      # per-group count
    ("momentum", 2, None, "microbatch", "final"),
    ("adamw", 3, None, "microbatch", "traj"),
]


@pytest.mark.parametrize("name,T,t_i,mode,metrics", LEAF_CASES)
def test_leaf_carry_round_parity(name, T, t_i, mode, metrics, key,
                                 monkeypatch):
    """The leaf-carrying round == the per-step flat round (the same
    elementwise math, fused per leaf instead of per buffer, so to 1e-6;
    counts and step counts exactly) == the pytree round, over two
    rounds."""
    params, batch = make_problem(key)
    G = 3
    if mode == "microbatch":
        batch = jax.tree.map(lambda x: jnp.stack([x * (1 + 0.1 * t)
                                                  for t in range(T)], 1),
                             batch)
    layout = packing.layout_of(params)
    opt_t = optim.get(name, 0.05)
    opt_p = optim.get(name, 0.05, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=T, t_i=t_i,
                              inner_mode=mode, metrics=metrics)
    leaf = lsgd.make_local_round(quad_loss, opt_p, cfg, layout=layout)
    assert leaf.buffer_path == "leaves"
    assert leaf.buffer_passes == 2 * (1 + len(MOMENT_KEYS[name]))
    _flat_only(monkeypatch)
    flat = lsgd.make_local_round(quad_loss, opt_p, cfg, layout=layout)
    assert flat.buffer_path == "flat"
    assert flat.buffer_passes == 2 * T + (metrics == "final")
    rnd_l, rnd_f = jax.jit(leaf), jax.jit(flat)
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    sl = lsgd.init_state(params, opt_p, n_groups=G, layout=layout)
    sf = jax.tree.map(jnp.copy, sl)
    st = lsgd.init_state(params, opt_t, n_groups=G)
    for _ in range(2):
        sl, ml = rnd_l(sl, batch)
        sf, mf = rnd_f(sf, batch)
        st, mt = rnd_t(st, batch)
    np.testing.assert_array_equal(sl["opt"]["count"], sf["opt"]["count"])
    for a, b in zip(jax.tree.leaves(sf), jax.tree.leaves(sl)):
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(ml["inner_steps"], mf["inner_steps"])
    for k in ("loss", "grad_sq", "grad_sq_first", "grad_sq_traj"):
        if k in mf:
            np.testing.assert_allclose(ml[k], mf[k], rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(lsgd.server_params(st)),
                    jax.tree.leaves(lsgd.server_params(sl, layout=layout))):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)
    for mk in MOMENT_KEYS[name]:
        for g in range(G):
            ref = packing.pack(
                jax.tree.map(lambda x: x[g], st["opt"][mk]), layout)
            np.testing.assert_allclose(sl["opt"][mk][g], ref,
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name,T,want", [
    ("momentum", 4, "leaves"), ("sgd", 1, "leaves"), ("adamw", 3, "leaves"),
    ("momentum", 1, "flat"), ("adamw", 2, "flat"),
    ("momentum", 4, "shardexec"),
])
def test_carries_leaves_rule(name, T, want, key):
    """S <= T carries the leaves; fewer local steps than streams, or a
    round on the sharded execution layer, keeps the flat buffers."""
    from jax.sharding import Mesh
    from repro.sharding.shardexec import ShardExec

    params, _ = make_problem(key)
    opt = optim.get(name, 0.05, packed=True, impl="jnp")
    S = packing.stream_layout_for(opt, packing.layout_of(params)).n_streams
    sexec = None
    layout = packing.layout_of(params)
    if want == "shardexec":
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        sexec = ShardExec(mesh=mesh, group_axes=("data",),
                          shard_axes=("model",))
        layout = packing.shard_layout(layout, sexec.n_shards)
    assert lsgd.carries_leaves(S, T, sexec) == (want == "leaves")
    cfg = lsgd.LocalSGDConfig(n_groups=1, inner_steps=T)
    rnd = lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                shardexec=sexec)
    path = "leaves" if want == "leaves" else "flat"
    assert rnd.buffer_path == path
    assert rnd.buffer_passes == (2 * S if path == "leaves" else 2 * T + 1)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, its sub-jaxprs' included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("T,path", [(4, "leaves"), (1, "flat")])
def test_scan_body_crosses_no_buffer(T, path, key):
    """The leaf-carrying local steps hold no concatenate to, and no slice
    of, a (G, N) buffer; the flat ones (the control) hold both."""
    params, batch = make_problem(key)
    G = 3
    layout = packing.layout_of(params)
    opt = optim.get("momentum", 0.05, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=T)
    rnd = lsgd.make_local_round(quad_loss, opt, cfg, layout=layout)
    assert rnd.buffer_path == path
    state = lsgd.init_state(params, opt, n_groups=G, layout=layout)
    closed = jax.make_jaxpr(rnd)(state, batch)
    scan, = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "scan"]
    body = list(_eqns(scan.params["jaxpr"].jaxpr))

    def touches_buffer(e):
        avals = ([v.aval for v in e.outvars] if e.primitive.name
                 == "concatenate" else [v.aval for v in e.invars])
        return any(getattr(a, "shape", ())[-1:] == (layout.padded,)
                   for a in avals)

    crossings = [e for e in body if e.primitive.name in ("concatenate",
                                                         "slice")
                 and touches_buffer(e)]
    assert bool(crossings) == (path == "flat")


@pytest.mark.parametrize("t_inner,path,passes", [
    ("2", "leaves", 4), ("1", "flat", 3)])
def test_buffer_passes_in_trace_meta(t_inner, path, passes, tmp_path,
                                     monkeypatch):
    from repro import obs
    from repro.launch import compile_cache, train
    from repro.obs import report

    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    out = tmp_path / "trace.jsonl"
    train.main(["--arch", "paper-mlp", "--reduced", "--packed", "--groups",
                "2", "--per-group", "2", "--seq", "16", "--rounds", "1",
                "--opt", "momentum", "--t-inner", t_inner,
                "--trace", str(out)])
    meta, _ = report.load(out)
    assert meta["schema"] == obs.SCHEMA_VERSION
    assert (meta["buffer_path"], meta["buffer_passes"]) == (path, passes)


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------


def test_packed_requires_layout_and_packed_opt(key):
    params, _ = make_problem(key)
    layout = packing.layout_of(params)
    cfg = lsgd.LocalSGDConfig(n_groups=2, inner_steps=2)
    with pytest.raises(ValueError):
        lsgd.make_local_round(quad_loss, optim.packed("sgd", 0.1), cfg)
    with pytest.raises(ValueError):
        lsgd.make_local_round(quad_loss, optim.sgd(0.1), cfg,
                              layout=layout)
    with pytest.raises(ValueError):
        lsgd.make_sync_step(quad_loss, optim.packed("sgd", 0.1))


def test_packed_unsupported_modes_raise(key):
    params, _ = make_problem(key)
    layout = packing.layout_of(params)
    opt_p = optim.packed("sgd", 0.1)
    with pytest.raises(NotImplementedError):
        lsgd.make_local_round(
            quad_loss, opt_p,
            lsgd.LocalSGDConfig(n_groups=2, inner_steps=2, threshold=1e-3),
            layout=layout)
    with pytest.raises(NotImplementedError):
        # the pytree path silently ignores t_i under microbatch; the
        # packed path refuses rather than silently diverging from it
        lsgd.make_local_round(
            quad_loss, opt_p,
            lsgd.LocalSGDConfig(n_groups=2, inner_steps=2, t_i=(1, 2),
                                inner_mode="microbatch"),
            layout=layout)


def test_build_packed_train_step_rejects_policy():
    from repro.configs.base import get_config, InputShape
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    mesh = make_local_mesh(1, 1)
    shape = InputShape(name="tiny", kind="train", global_batch=4,
                       seq_len=8)
    with pytest.raises(NotImplementedError):
        build_train_step(cfg, shape, mesh, packed=True, policy="dp")


# ---------------------------------------------------------------------------
# packed train-step builder + donation
# ---------------------------------------------------------------------------


def test_build_packed_train_step():
    from repro.configs.base import get_config, InputShape
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    mesh = make_local_mesh(1, 1)
    shape = InputShape(name="tiny", kind="train", global_batch=4,
                       seq_len=8)
    built = build_train_step(cfg, shape, mesh, t_inner=2, opt_name="adamw",
                             packed=True)
    assert built.donate_argnums == (0,)
    assert built.meta["packed"] is True
    state_abs, batch_abs = built.args
    n = built.meta["n_flat"]
    assert state_abs["params"].shape[-1] == n
    assert state_abs["opt"]["m"].shape == state_abs["params"].shape
    # lower+compile on the host mesh to prove the packed round is jittable
    jitted = jax.jit(built.fn, donate_argnums=built.donate_argnums)
    jitted.lower(*built.args).compile()


def test_fused_ops_donation_memory_analysis():
    """ops.fused_adamw donates p/m/v: the compiled memory analysis must
    show the donated bytes as aliased (no extra output copies)."""
    from repro.kernels import ops

    n = 4096
    p = jax.ShapeDtypeStruct((n,), jnp.float32)
    c = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = ops.fused_adamw.lower(p, p, p, p, c, 1e-3)
    ma = lowered.compile().memory_analysis()
    if ma is None or not hasattr(ma, "alias_size_in_bytes"):
        pytest.skip("backend exposes no memory analysis")
    # p, m, v donated -> at least 3 * n * 4 bytes aliased in place, and
    # no un-aliased full-buffer output copy remains
    assert ma.alias_size_in_bytes >= 3 * n * 4
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < n * 4

    lowered = ops.fused_sgd.lower(p, p, 1e-3)
    ma = lowered.compile().memory_analysis()
    assert ma.alias_size_in_bytes >= n * 4


# ---------------------------------------------------------------------------
# StreamLayout: the multi-stream payload contract (DESIGN.md §10)
# ---------------------------------------------------------------------------


def test_stream_layout_contract(key):
    params, _ = make_problem(key)
    layout = packing.layout_of(params)
    for name, streams in (("sgd", ("params",)),
                          ("momentum", ("params", "mu")),
                          ("adamw", ("params", "m", "v"))):
        opt = optim.packed(name, 0.1, impl="jnp")
        sl = packing.stream_layout_for(opt, layout)
        assert sl.streams == streams
        assert sl.moment_streams == streams[1:]
        assert sl.n_streams == len(streams)
        assert sl.sizes() == {s: layout.padded for s in streams}
        # abstract matches what opt.init actually allocates
        buf_G = layout.abstract((3,))
        opt_abs = jax.eval_shape(opt.init, buf_G)
        abs_ = sl.abstract((3,))
        for s in sl.moment_streams:
            assert opt_abs[s].shape == abs_[s].shape
    # the declared streams ARE the state's non-count keys
    opt = optim.packed("adamw", 0.1, impl="jnp")
    state = opt.init(packing.pack(params, layout))
    assert set(opt.moment_keys) == set(state) - {"count"}


def test_stream_layout_stacked_view(key):
    """stack/unstack: one (S, ..., Np) view of the whole payload for
    fused whole-payload kernels — round-trips exactly, streams in
    declared order."""
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params), 2, align=64)
    opt = optim.packed("adamw", 0.1, impl="jnp")
    sl = packing.stream_layout_for(opt, layout)
    G = 3
    ks = jax.random.split(key, sl.n_streams)
    bufs = {name: jax.random.normal(k, (G, layout.padded))
            for name, k in zip(sl.streams, ks)}
    stacked = sl.stack(bufs)
    assert stacked.shape == (3, G, layout.padded)
    np.testing.assert_array_equal(stacked[sl.index("m")], bufs["m"])
    back = sl.unstack(stacked)
    for name in sl.streams:
        np.testing.assert_array_equal(back[name], bufs[name])


def test_builder_meta_wire_bytes_by_stream():
    """The packed builder's meta resolves wire bytes per stream and the
    totals are exact sums (adamw + int8 moments)."""
    from repro.configs.base import get_config, InputShape
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_train_step

    from repro import comm

    cfg = get_config("paper-mlp").reduced()
    mesh = make_local_mesh(1, 1)
    shape = InputShape(name="tiny", kind="train", global_batch=4,
                       seq_len=8)
    built = build_train_step(cfg, shape, mesh, t_inner=2, opt_name="adamw",
                             packed=True, codec="int8",
                             moment_codec="int8")
    meta = built.meta
    assert meta["streams"] == ["params", "m", "v"]
    by = meta["wire_bytes_per_round_by_stream"]
    assert set(by) == {"params", "m", "v"}
    assert meta["wire_bytes_per_round"] == sum(by.values())
    n = meta["n_flat_padded"]
    ex = comm.get_exchange("server", "int8", meta["groups"],
                           moment_codec="int8")
    assert by == ex.wire_bytes_by_stream(n, {"m": n, "v": n})
    # comm state carries the three per-stream rng counters
    assert set(built.args[0]["comm"]["codec"]) == {"params", "m", "v"}
