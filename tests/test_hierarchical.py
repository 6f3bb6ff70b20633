"""Tiered fault domains: hierarchical two-tier exchange (ISSUE 10 /
DESIGN.md §16).

Acceptance-critical invariants:
  * the lossless two-tier round is the composition of the pod-local
    circulant (or pod mean) and one pod-graph consensus hop — verified
    against a straight numpy reference;
  * cross-tier push_sum stays ratio consensus: sum(mass) +
    sum(backlog_w) == G EXACTLY under DCN loss, the group mean is
    unbiased where flat gossip under the same loss rate drifts;
  * pod-leader dropout re-elects deterministically; a fully-partitioned
    pod degrades to pod-local rounds and rejoins by draining queued
    mass, conserving it exactly;
  * the seed-lane registry (faults.HASH_LANES / CODEC_SEED_OFFSETS /
    FAULT_SEED_OFFSETS) is collision-free and bit-stable with the
    historical seed derivations;
  * a mid-fault checkpoint with live tiered backlogs resumes bit-exact;
  * the sharded (shard_map) hierarchical path matches the replicated
    one under identical per-tier fault schedules;
  * every §13 round record carries the per-tier keys and the wire total
    decomposes as intra + inter.

8-device tests ride the same forced-host child-process pattern as
tests/test_shardexec.py / tests/test_faults.py.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, obs, optim
from repro.comm import faults as faults_mod
from repro.comm import topology as topo
from repro.comm.exchange import elect_leaders
from repro.core import localsgd as lsgd
from repro.optim import packing
from repro.sharding import shardexec as shx

HAVE8 = jax.device_count() >= 8
needs8 = pytest.mark.skipif(not HAVE8, reason="needs 8 devices "
                            "(forced-host child process runs these)")

G = 4


def quad_loss(params, batch):
    r = batch["A"] @ params["w"] - batch["b"]
    return 0.5 * jnp.sum(r ** 2)


def make_problem(key, g=G, r=8, d=40):
    ks = jax.random.split(key, 3)
    A = jax.random.normal(ks[0], (g, r, d)) / np.sqrt(d)
    w_star = jax.random.normal(ks[1], (d,))
    batch = {"A": A, "b": jnp.einsum("grd,d->gr", A, w_star)}
    params = {"w": jax.random.normal(ks[2], (d,))}
    return params, batch


def mesh8(shape=(4, 2), axes=("data", "model")):
    from jax.sharding import Mesh
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


def mass_total(st):
    return float(jnp.sum(st["mass"]) + jnp.sum(st["backlog_w"]))


def run_rounds(ex, x, n_rounds, every=None):
    """Iterate the exchange as a pure consensus map on one (G, d)
    params stream; ``every(st)`` checks per-round invariants."""
    st = ex.init(x)
    fn = jax.jit(ex.streams)
    xs = {"params": jnp.asarray(x)}
    xs0 = {"params": jnp.asarray(x)} if ex.lossy_stream("params") else {}
    for _ in range(n_rounds):
        xs, st = fn(xs, dict(xs0), st)
        if every is not None:
            every(st)
    return np.asarray(xs["params"]), st


# ---------------------------------------------------------------------------
# lossless round: numpy reference
# ---------------------------------------------------------------------------


def _ref_hier_round(x, n_pods, mix_rounds=1):
    """One lossless fp32 hierarchical round (ring intra, push_sum
    inter): pod-local circulant hops then one pod-graph consensus hop.
    All offset sets are symmetric (±1 patterns), so the stencil is
    direction-free."""
    g = x.shape[0]
    s = g // n_pods
    y = x.astype(np.float64).copy()

    def pod_take(v, d):
        r = v.reshape((n_pods, s) + v.shape[1:])
        return np.roll(r, -d, axis=1).reshape(v.shape)

    if s > 1:
        w_self, offs, w_edge = topo.ring_circulant(s)
        for _ in range(mix_rounds):
            out = w_self * y
            for d in offs:
                out = out + w_edge * pod_take(y, d)
            y = out
    offs_p = topo.push_sum_offsets(n_pods)
    if offs_p:
        a = 1.0 / (len(offs_p) + 1)
        z = a * y.copy()
        for dp in offs_p:
            z = z + a * np.roll(y, dp * s, axis=0)
        y = z
    return y


@pytest.mark.parametrize("g,n_pods,mix_rounds", [
    (4, 2, 1), (8, 2, 2), (8, 4, 1), (6, 3, 1),
])
def test_lossless_round_matches_numpy_reference(g, n_pods, mix_rounds,
                                                key):
    x = jax.random.normal(key, (g, 24))
    ex = comm.get_exchange("hierarchical", "fp32", g, n_pods=n_pods,
                           mix_rounds=mix_rounds)
    out, st = run_rounds(ex, x, 1)
    ref = _ref_hier_round(np.asarray(x), n_pods, mix_rounds)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    # lossless: the global mean is preserved exactly-to-rounding and the
    # weight channel stays uniform (no mass ever queues)
    np.testing.assert_allclose(out.mean(0), np.asarray(x).mean(0),
                               rtol=1e-5, atol=1e-6)
    if "mass" in st:
        np.testing.assert_allclose(np.asarray(st["mass"]), 1.0,
                                   rtol=1e-6)
        assert float(jnp.sum(st["backlog_w"])) == 0.0


def test_lossless_server_server_is_exact_global_mean(key):
    """intra=server takes pod means, inter=server averages the leaders:
    with equal pods one round lands every lane on the global mean."""
    x = jax.random.normal(key, (8, 16))
    ex = comm.get_exchange("hierarchical", "fp32", 8, n_pods=4,
                           intra_topology="server",
                           inter_topology="server")
    out, _ = run_rounds(ex, x, 1)
    np.testing.assert_allclose(
        out, np.broadcast_to(np.asarray(x).mean(0), out.shape),
        rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# cross-tier push_sum: mass conservation + unbiasedness under DCN loss
# ---------------------------------------------------------------------------


def test_mass_conserved_and_unbiased_under_dcn_loss(key):
    g, n_pods = 8, 4
    x = jax.random.normal(key, (g, 40))
    true_mean = np.asarray(x).mean(0)
    ex = comm.get_exchange("hierarchical", "fp32", g, n_pods=n_pods,
                           drop_rate=0.2, stall_rate=0.1, fault_seed=5)
    checks = []
    out, st = run_rounds(ex, x, 60,
                         every=lambda s: checks.append(mass_total(s)))
    # THE §12/§16 invariant, every single round: no mass is ever lost to
    # a dropped DCN packet — it queues in the per-edge backlog
    assert all(c == pytest.approx(g, abs=1e-3) for c in checks)
    # ratio consensus: every lane converges to the TRUE group mean
    err = np.abs(out - true_mean[None]).max()
    assert err < 1e-3, err
    bias = np.abs(out.mean(0) - true_mean).max()
    assert bias < 1e-4, bias


def test_tiered_push_sum_unbiased_where_flat_gossip_drifts(key):
    """The §16 bias regression at the ISSUE's 5-10%% DCN loss: under the
    same loss rate, flat gossip's self-substituted rows stay stochastic
    but not doubly — the group mean drifts — while the tiered push_sum
    estimate stays unbiased."""
    g, loss = 8, 0.075
    x = jax.random.normal(key, (g, 40))
    true_mean = np.asarray(x).mean(0)
    hier = comm.get_exchange("hierarchical", "fp32", g, n_pods=4,
                             drop_rate=loss, fault_seed=2)
    goss = comm.get_exchange("gossip", "fp32", g, drop_rate=loss,
                             fault_seed=2)
    out_h, st_h = run_rounds(hier, x, 40)
    out_g, _ = run_rounds(goss, x, 40)
    err_h = np.linalg.norm(out_h.mean(0) - true_mean)
    err_g = np.linalg.norm(out_g.mean(0) - true_mean)
    assert err_h < 1e-3, err_h
    assert err_g > 10 * err_h, (err_g, err_h)
    assert mass_total(st_h) == pytest.approx(g, abs=1e-3)


# ---------------------------------------------------------------------------
# leader election + partitioned-pod degradation
# ---------------------------------------------------------------------------


def test_leader_election_deterministic_and_survives_dropout():
    full = jnp.ones((6,), jnp.float32)
    w, live = elect_leaders(full, 3)
    np.testing.assert_array_equal(np.asarray(w), [1, 0, 1, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(live), [1, 1, 1])
    # leader dropout -> the next live member takes over, pod stays live
    w2, live2 = elect_leaders(full.at[0].set(0.0), 3)
    np.testing.assert_array_equal(np.asarray(w2), [0, 1, 1, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(live2), [1, 1, 1])
    # fully-dead pod: zero weight, pod_live 0 — no phantom leader
    w3, live3 = elect_leaders(full.at[2].set(0.0).at[3].set(0.0), 3)
    np.testing.assert_array_equal(np.asarray(w3), [1, 0, 0, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(live3), [1, 0, 1])
    # pure in the mask: repeated calls agree bit-for-bit
    wa, la = elect_leaders(w3, 3)
    wb, lb = elect_leaders(w3, 3)
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_partitioned_pod_degrades_then_rejoins_exactly(key):
    """Pod 1 (lanes 2-3) loses its DCN uplink for rounds [2, 5): during
    the outage it runs pod-local rounds only — its pod mean is frozen —
    the queued cross-pod mass is conserved EXACTLY, and after rejoin the
    drained backlog pulls everyone to the true global mean."""
    x = jax.random.normal(key, (G, 32))
    true_mean = np.asarray(x).mean(0)
    ex = comm.get_exchange("hierarchical", "fp32", G, n_pods=2,
                           dropouts=((2, 2, 5), (3, 2, 5)),
                           fault_seed=1)
    st = ex.init(x)
    fn = jax.jit(ex.streams)
    xs = {"params": jnp.asarray(x)}
    pod1_mean = None
    for rnd in range(24):
        xs, st = fn(xs, {}, st)
        assert mass_total(st) == pytest.approx(G, abs=1e-3), rnd
        cur = np.asarray(xs["params"])[2:4].mean(0)
        if rnd == 2:
            pod1_mean = cur
        elif rnd in (3, 4):
            # degraded to local-only: intra mixing preserves the pod
            # mean, the dead inter tier injects nothing
            np.testing.assert_allclose(cur, pod1_mean, rtol=1e-5,
                                       atol=1e-6)
    out = np.asarray(xs["params"])
    np.testing.assert_allclose(out, np.broadcast_to(true_mean, out.shape),
                               atol=1e-3)
    assert np.abs(out.mean(0) - true_mean).max() < 1e-4


# ---------------------------------------------------------------------------
# seed-lane registry (satellite: centralized splitmix32 lanes)
# ---------------------------------------------------------------------------


def test_seed_registry_collision_free_and_bit_stable():
    """The registries in repro.comm.faults are the ONE home for every
    derived seed/lane: no two entries of a registry may collide (a
    collision silently correlates independent randomness), and the
    derivations must stay bit-stable with the historical constants
    (seed, seed+1, seed+2) that shipped before the registry existed."""
    for reg in (faults_mod.HASH_LANES, faults_mod.CODEC_SEED_OFFSETS,
                faults_mod.FAULT_SEED_OFFSETS):
        assert len(set(reg.values())) == len(reg), reg
    for seed in (0, 7, 12345):
        cs = [faults_mod.codec_seed(seed, lane)
              for lane in faults_mod.CODEC_SEED_OFFSETS]
        assert len(set(cs)) == len(cs)
        fs = [faults_mod.fault_seed_for(seed, tier)
              for tier in faults_mod.FAULT_SEED_OFFSETS]
        assert len(set(fs)) == len(fs)
        # historical bit-exactness: params/moments/downlink were seeded
        # seed/seed+1/seed+2 before the registry centralized them
        assert faults_mod.codec_seed(seed, "params") == seed
        assert faults_mod.codec_seed(seed, "moments") == seed + 1
        assert faults_mod.codec_seed(seed, "downlink") == seed + 2
        assert faults_mod.fault_seed_for(seed, "flat") == seed
    with pytest.raises(ValueError):
        faults_mod.codec_seed(0, "no_such_lane")
    with pytest.raises(ValueError):
        faults_mod.fault_seed_for(0, "no_such_tier")
    # the two tiers of one fault_seed draw decorrelated mask streams
    pi = faults_mod.FaultPlan(
        seed=faults_mod.fault_seed_for(3, "intra"), drop_rate=0.3)
    px = faults_mod.FaultPlan(
        seed=faults_mod.fault_seed_for(3, "inter"), drop_rate=0.3)
    diff = sum(not np.array_equal(np.asarray(pi.push_mask(r, 64)),
                                  np.asarray(px.push_mask(r, 64)))
               for r in range(8))
    assert diff >= 6


# ---------------------------------------------------------------------------
# refusal matrix
# ---------------------------------------------------------------------------


def _assert_lists_alternatives(err, *names):
    msg = str(err.value)
    assert "valid" in msg, msg
    listed = [n for n in names if f"'{n}'" in msg]
    assert len(listed) >= 2, (msg, names)


def test_hierarchical_refusals_name_alternatives():
    gx = dict(n_groups=G, n_pods=2)
    with pytest.raises(ValueError) as e:      # non-divisor pod count
        comm.get_exchange("hierarchical", "fp32", G, n_pods=3)
    assert "divide" in str(e.value)
    with pytest.raises(ValueError) as e:      # tier knobs on flat topo
        comm.get_exchange("ring", "fp32", G, n_pods=2)
    assert "hierarchical" in str(e.value)
    with pytest.raises(ValueError):
        comm.get_exchange("ring", "fp32", G, inter_codec="int8")
    with pytest.raises(ValueError):
        comm.get_exchange("ring", "fp32", G, intra_drop_rate=0.1)
    with pytest.raises(ValueError) as e:      # unknown tier topologies
        comm.get_exchange("hierarchical", "fp32", **gx,
                          intra_topology="mesh")
    _assert_lists_alternatives(e, *comm.exchange.INTRA_TOPOLOGIES)
    with pytest.raises(ValueError) as e:
        comm.get_exchange("hierarchical", "fp32", **gx,
                          inter_topology="mesh")
    _assert_lists_alternatives(e, *comm.exchange.INTER_TOPOLOGIES)
    with pytest.raises(NotImplementedError) as e:   # delta intra codec
        comm.get_exchange("hierarchical", "int8", **gx)
    _assert_lists_alternatives(e, "fp32", "fp16", "bf16")
    with pytest.raises(NotImplementedError) as e:   # push_sum + int8
        comm.get_exchange("hierarchical", "fp32", **gx,
                          inter_codec="int8")
    _assert_lists_alternatives(e, "fp32", "fp16", "bf16")
    with pytest.raises(NotImplementedError) as e:   # topk cross-tier
        comm.get_exchange("hierarchical", "fp32", **gx,
                          inter_codec="topk")
    _assert_lists_alternatives(e, "fp32", "fp16", "bf16", "int8")
    with pytest.raises(NotImplementedError) as e:   # lossy inter-server
        comm.get_exchange("hierarchical", "fp32", **gx,
                          inter_topology="server", drop_rate=0.1)
    assert "push_sum" in str(e.value)
    with pytest.raises(NotImplementedError) as e:
        comm.get_exchange("hierarchical", "fp32", **gx, overlap=True)
    _assert_lists_alternatives(e, "server", "ring", "gossip")
    with pytest.raises(NotImplementedError) as e:
        comm.get_exchange("hierarchical", "fp32", **gx,
                          downlink_codec="int8")
    assert "inter_codec" in str(e.value)


def test_flat_fault_plan_on_hierarchical_refused(key):
    """A flat FaultPlan does not say which tier it masks — the exchange
    refuses it instead of guessing."""
    import dataclasses
    x = jax.random.normal(key, (G, 8))
    ex = comm.get_exchange("hierarchical", "fp32", G, n_pods=2)
    bad = dataclasses.replace(
        ex, fault_plan=faults_mod.FaultPlan(seed=0, drop_rate=0.2))
    with pytest.raises(NotImplementedError) as e:
        bad.streams({"params": x}, {}, bad.init(x))
    assert "TieredFaultPlan" in str(e.value)


# ---------------------------------------------------------------------------
# checkpoint: mid-fault resume with tiered backlogs is bit-exact
# ---------------------------------------------------------------------------


def test_checkpoint_resume_mid_fault_tiered_backlogs(key, tmp_path):
    """Save at round 3 with live per-tier fault schedules and queued
    cross-pod backlog mass, resume, and the continuation is bit-exact
    with the uninterrupted run — both tier's masks are pure in
    (round, tier seed lane), so the schedule replays."""
    from repro.checkpoint import io as ckpt_io

    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("momentum", 0.05, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("hierarchical", "fp32", G, n_pods=2,
                           drop_rate=0.4, stall_rate=0.1,
                           intra_drop_rate=0.1, fault_seed=4, impl="jnp")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    for _ in range(3):
        st, _ = rnd(st, batch)
    assert int(st["comm"]["round"]) == 3
    # mid-fault for real: queued cross-pod mass is in flight (the fault
    # schedule is pure in (round, seed), so this is deterministic)
    assert float(jnp.sum(st["comm"]["backlog_w"])) > 0.0
    assert mass_total(st["comm"]) == pytest.approx(G, abs=1e-3)
    path = str(tmp_path / "mid_fault_tiered")
    ckpt_io.save(path, st, metadata={"round": 3, "comm": ex.name})
    back = ckpt_io.load(path, st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for _ in range(3):
        st, _ = rnd(st, batch)            # uninterrupted
        back, _ = rnd(back, batch)        # resumed
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# §13 round metrics: per-tier keys + wire identity
# ---------------------------------------------------------------------------


def test_round_metrics_carry_tier_keys_and_wire_identity(key):
    params, batch = make_problem(key)
    opt = optim.get("sgd", 0.05)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("hierarchical", "fp32", G, n_pods=2,
                           drop_rate=0.2, intra_drop_rate=0.05,
                           fault_seed=3)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, exchange=ex)
    st, m = rnd(st, batch)
    m = {**m, **rnd.wire_bytes(st)}
    assert set(obs.round_metric_keys(("params",))) <= set(m)
    assert int(m["wire_bytes"]) \
        == int(m["wire_bytes_intra"]) + int(m["wire_bytes_inter"])
    assert int(m["wire_bytes_intra"]) > 0
    assert int(m["wire_bytes_inter"]) > 0
    for k in ("participation", "participation_intra",
              "participation_inter", "delivery_rate",
              "delivery_rate_intra", "delivery_rate_inter"):
        assert 0.0 <= float(m[k]) <= 1.0, (k, float(m[k]))
    assert float(m["delivery_rate_intra"]) \
        == pytest.approx(ex.delivery_rate_intra)
    assert float(m["delivery_rate_inter"]) \
        == pytest.approx(ex.delivery_rate_inter)
    # flat rounds carry the same keys with the single-tier conventions
    ex_flat = comm.get_exchange("ring", "fp32", G)
    rnd_f = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                          exchange=ex_flat))
    st_f = lsgd.init_state(params, opt, n_groups=G, exchange=ex_flat)
    _, mf = rnd_f(st_f, batch)
    mf = {**mf, **rnd_f.wire_bytes(st_f)}
    assert set(obs.round_metric_keys(("params",))) <= set(mf)
    assert int(mf["wire_bytes_intra"]) == int(mf["wire_bytes"])
    assert int(mf["wire_bytes_inter"]) == 0
    assert float(mf["participation_inter"]) == 1.0
    assert float(mf["delivery_rate_inter"]) == 1.0


def test_adaptive_t_prices_tiers_on_their_own_links():
    """AdaptiveT.from_exchange prices the intra bytes on the fast link
    and the inter bytes on the DCN at the inter tier's delivery rate —
    slowing or losing the DCN makes comm pricier (smaller r, T* up)."""
    from repro.core.controller import AdaptiveT

    ex = comm.get_exchange("hierarchical", "fp32", G, n_pods=2,
                           inter_codec="bf16", drop_rate=0.1)
    fast = AdaptiveT.from_exchange(1e-3, ex, 1_000_000)
    slow = AdaptiveT.from_exchange(1e-3, ex, 1_000_000,
                                   inter_bandwidth_bytes_per_s=5e9)
    assert slow.r < fast.r
    # intra ring prices ATTEMPTS, so an intra loss rate raises the priced
    # cost (inter push_sum prices delivered edges — loss there cancels)
    lossless = comm.get_exchange("hierarchical", "fp32", G, n_pods=2,
                                 inter_codec="bf16")
    lossy_ici = comm.get_exchange("hierarchical", "fp32", G, n_pods=2,
                                  inter_codec="bf16", intra_drop_rate=0.2)
    assert (AdaptiveT.from_exchange(1e-3, lossy_ici, 1_000_000).r
            < AdaptiveT.from_exchange(1e-3, lossless, 1_000_000).r)


# ---------------------------------------------------------------------------
# 8-device mesh: sharded hierarchical parity + builder threading
# ---------------------------------------------------------------------------


def _packed_setup(key, sexec):
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    x0 = packing.pack(lsgd.replicate(params, G), layout)
    mask = (jnp.arange(layout.padded) < layout.size).astype(jnp.float32)
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1),
                               x0.shape) * 0.1 * mask
    return layout, x0, x


@needs8
@pytest.mark.parametrize("codec,kw", [
    ("fp32", dict(drop_rate=0.3, stall_rate=0.1, intra_drop_rate=0.05)),
    ("bf16", dict(drop_rate=0.08, stall_rate=0.05, inter_codec="bf16")),
    ("fp32", dict(intra_topology="server", inter_topology="server",
                  inter_codec="int8", intra_stall_rate=0.1)),
])
def test_sharded_hierarchical_matches_replicated(codec, kw, key):
    """THE §16 shard_map gate: per-tier masks, leader election inputs
    and int8 noise are generated OUTSIDE the shard_map block at full
    (G,) shape, so the sharded two-tier round consumes IDENTICAL fault
    schedules — outputs match the replicated path to reduction order
    and the mass/participation channels agree exactly."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    layout, x0, x = _packed_setup(key, sexec)
    ex = comm.get_exchange("hierarchical", codec, G, n_pods=2,
                           impl="jnp", fault_seed=6, **kw)
    st = ex.init(x0)
    fs = jax.jit(sexec.exchange_streams(ex, layout))
    fr = jax.jit(ex.streams)
    xs = {"params": x}
    xs0 = {"params": x0} if ex.lossy_stream("params") else {}
    os_, ss = fs(dict(xs), dict(xs0), st)
    or_, sr = fr(dict(xs), dict(xs0), st)
    np.testing.assert_allclose(np.asarray(os_["params"]),
                               np.asarray(or_["params"]),
                               rtol=1e-4, atol=1e-4)
    for k in ("participation", "participation_intra",
              "participation_inter"):
        assert float(ss[k]) == pytest.approx(float(sr[k]))
    assert int(ss["round"]) == int(sr["round"]) == 1
    if ex.inter_topology == "push_sum":
        np.testing.assert_allclose(np.asarray(ss["mass"]),
                                   np.asarray(sr["mass"]),
                                   rtol=1e-6, atol=1e-7)
        assert mass_total(ss) == pytest.approx(G, abs=1e-3)
        assert mass_total(sr) == pytest.approx(G, abs=1e-3)


@needs8
def test_sharded_hierarchical_multi_round_conserves_mass(key):
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    layout, x0, x = _packed_setup(key, sexec)
    ex = comm.get_exchange("hierarchical", "fp32", G, n_pods=2,
                           drop_rate=0.2, stall_rate=0.1, fault_seed=3)
    fs = jax.jit(sexec.exchange_streams(ex, layout))
    fr = jax.jit(ex.streams)
    ss = sr = ex.init(x0)
    xs_s = xs_r = x
    for _ in range(6):
        o_s, ss = fs({"params": xs_s}, {}, ss)
        o_r, sr = fr({"params": xs_r}, {}, sr)
        xs_s, xs_r = o_s["params"], o_r["params"]
        np.testing.assert_allclose(np.asarray(xs_s), np.asarray(xs_r),
                                   rtol=1e-4, atol=1e-4)
        assert mass_total(ss) == pytest.approx(G, abs=1e-3)
        assert mass_total(sr) == pytest.approx(G, abs=1e-3)


@needs8
def test_builder_threads_hierarchical_flags_sharded():
    """build_train_step threads --n-pods/--intra-*/--inter-* through to
    the exchange, allocates the cross-tier mass/backlog state with
    buffer-aligned shardings, reports the per-tier wire split in its
    meta, and the tiered faulty step compiles on the mesh."""
    from repro.configs.base import InputShape, get_config
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    mesh = mesh8()
    shape = InputShape(name="tiny", kind="train", global_batch=8,
                       seq_len=8)
    built = build_train_step(cfg, shape, mesh, t_inner=2, packed=True,
                             comm="hierarchical", codec="fp32",
                             n_pods=2, drop_rate=0.1,
                             intra_drop_rate=0.05, fault_seed=3)
    assert built.meta["comm"].startswith("hier[")
    by_tier = built.meta["wire_bytes_per_round_by_tier"]
    assert set(by_tier) == {"intra", "inter"}
    assert by_tier["intra"] > 0 and by_tier["inter"] > 0
    state_abs, _ = built.args
    assert {"mass", "backlog", "backlog_w", "round", "participation",
            "participation_intra", "participation_inter"} \
        <= set(state_abs["comm"])
    bl = state_abs["comm"]["backlog"]["params"]
    psh = built.in_shardings[0]["params"]
    bsh = built.in_shardings[0]["comm"]["backlog"]["params"]
    assert bsh.shard_shape(tuple(bl.shape))[1:] \
        == psh.shard_shape(tuple(state_abs["params"].shape))
    with mesh:
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=built.donate_argnums)
        jitted.lower(*built.args).compile()


# ---------------------------------------------------------------------------
# tier-1 driver: force 8 host devices in a child process
# ---------------------------------------------------------------------------


def test_suite_under_forced_8_devices():
    """Under the plain 1-device tier-1 run, re-run this module with 8
    forced host devices in a subprocess (jax locks the device count at
    first init). CI's forced-8-device job runs the tests directly and
    skips this driver (REPRO_SHARDEXEC_CHILD, shared with
    test_shardexec.py)."""
    if HAVE8:
        pytest.skip("already running with 8 devices")
    if os.environ.get("REPRO_SHARDEXEC_CHILD") == "1":
        pytest.skip("child process")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["REPRO_SHARDEXEC_CHILD"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=1800,
        cwd=repo)
    assert r.returncode == 0, (
        f"8-device hierarchical suite failed:\n{r.stdout[-4000:]}"
        f"\n{r.stderr[-2000:]}")
