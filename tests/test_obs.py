"""Observability subsystem (repro.obs + the round telemetry contract).

ISSUE 7 invariants:
  * wire-byte identities hold across the full topology x codec x faults
    matrix: total == sum of the per-stream splits, and total == up + down
    (server/async: pushes and replies are distinct payloads) or
    total == up == down (p2p edges count once) — including push_sum's
    delivered-priced accounting,
  * every localsgd round emits the UNIFORM metric schema
    (obs.round_metric_keys) regardless of topology/codec/faults —
    participation/delivery_rate are 1.0 on a clean network, not absent,
  * a trace written through obs.Trace round-trips through
    obs.report.load/check/summarize: schema-valid, monotone rounds,
    fenced phase durations,
  * consensus distance ||x_g - mean||^2 matches replicated-vs-sharded
    <= 1e-5 on the forced-8-device mesh (shardexec.consensus_sq_groups).

The 8-device tests re-run in a forced-host child under plain tier-1
(same driver pattern as test_shardexec).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, obs, optim
from repro.core import localsgd as lsgd
from repro.obs import report
from repro.optim import packing

HAVE8 = jax.device_count() >= 8
needs8 = pytest.mark.skipif(not HAVE8, reason="needs 8 devices "
                            "(forced-host child process runs these)")

G = 4


def quad_loss(params, batch):
    r = batch["A"] @ params["w"] - batch["b"]
    return 0.5 * jnp.sum(r ** 2) + 0.1 * jnp.sum(params["u"] ** 2)


def make_problem(key, g=G, r=4, d=6):
    ks = jax.random.split(key, 4)
    A = jax.random.normal(ks[0], (g, r, d)) / np.sqrt(d)
    w_star = jax.random.normal(ks[1], (d,))
    batch = {"A": A, "b": jnp.einsum("grd,d->gr", A, w_star)}
    params = {"w": jax.random.normal(ks[2], (d,)),
              "u": jax.random.normal(ks[3], (2, 3))}
    return params, batch


# ---------------------------------------------------------------------------
# wire-byte identities across the topology x codec x faults matrix
# ---------------------------------------------------------------------------

TOPOLOGIES = ["server", "ring", "gossip", "async_stale", "push_sum"]
CODECS = ["fp32", "fp16", "bf16", "int8", "topk"]
FAULTS = [{}, {"drop_rate": 0.05, "fault_seed": 3},
          {"stall_rate": 0.1, "fault_seed": 7}]


def _matrix():
    for topo in TOPOLOGIES:
        for codec in CODECS:
            for faults in FAULTS:
                yield topo, codec, faults


def test_wire_bytes_identities_across_matrix():
    """Static accounting property: for every buildable combo (refused
    ones — push_sum+int8/topk, async_stale+topk — are skipped) the
    per-stream splits sum to the total, and the total follows the
    counting rule: p2p edge payloads count ONCE (total == up == down),
    server/async pushes and replies are distinct (total == up + down)."""
    n, msizes = 10_000, {"mu": 10_000}
    checked = 0
    for topo, codec, faults in _matrix():
        try:
            ex = comm.get_exchange(topo, codec, G, **faults)
        except NotImplementedError:
            continue
        for ms in ({}, msizes):
            by = ex.wire_bytes_by_stream(n, ms)
            total = ex.wire_bytes_per_round(n, moment_sizes=ms)
            up = ex.wire_bytes_up(n, moment_sizes=ms)
            down = ex.wire_bytes_down(n, moment_sizes=ms)
            label = f"{topo}/{codec}/{faults}/{sorted(ms)}"
            assert set(by) == {"params"} | set(ms), label
            assert total == sum(by.values()), label
            if ex.p2p:
                assert total == up == down, label
            else:
                assert total == up + down, label
            assert total > 0 and up > 0, label
        checked += 1
    # the matrix is real: every topology survives with >= 3 codecs
    assert checked >= 5 * 3


def test_push_sum_delivered_pricing_scales_wire_bytes():
    """push_sum prices DELIVERED payloads: a 20% drop rate scales the
    static per-round bytes by the expected delivery rate (and the
    payload carries the +4B weight counter per push)."""
    n = 5_000
    clean = comm.get_exchange("push_sum", "fp32", G)
    lossy = comm.get_exchange("push_sum", "fp32", G, drop_rate=0.2,
                              fault_seed=1)
    assert clean.delivery_rate == 1.0
    assert 0.0 < lossy.delivery_rate < 1.0
    b_clean = clean.wire_bytes_per_round(n)
    b_lossy = lossy.wire_bytes_per_round(n)
    assert b_lossy == pytest.approx(
        b_clean * lossy.delivery_rate / clean.delivery_rate, rel=0.01)


# ---------------------------------------------------------------------------
# uniform round-metric schema (device-side layer)
# ---------------------------------------------------------------------------

def _run_round(key, topo, codec, opt_name="sgd", packed=True, avg=False,
               rounds=1, **faults):
    params, batch = make_problem(key)
    layout = packing.layout_of(params) if packed else None
    opt = (optim.packed(opt_name, 0.05, impl="jnp") if packed
           else optim.get(opt_name, 0.05))
    ex = comm.get_exchange(topo, codec, G, **faults)
    avg = avg and ex.supports_opt_state_averaging
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2,
                              average_opt_state=avg)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex, average_opt_state=avg)
    for _ in range(rounds):
        st, m = rnd(st, batch)
    return ex, st, {**m, **rnd.wire_bytes(st)}


@pytest.mark.parametrize("topo,codec,opt_name,avg,faults", [
    ("server", "fp32", "sgd", False, {}),
    ("server", "int8", "momentum", True, {"drop_rate": 0.3,
                                          "fault_seed": 1}),
    ("ring", "topk", "sgd", False, {}),
    ("push_sum", "fp16", "sgd", False, {"drop_rate": 0.1,
                                        "fault_seed": 2}),
    ("async_stale", "fp32", "adamw", True, {}),
])
def test_uniform_round_metric_schema(key, topo, codec, opt_name, avg,
                                     faults):
    """EVERY configuration emits exactly obs.round_metric_keys(streams):
    consensus pre/post, per-stream codec error, backlog, participation,
    delivery — present (and finite) even where the quantity is trivially
    zero/one, so consumers never branch on key existence."""
    ex, st, m = _run_round(key, topo, codec, opt_name=opt_name, avg=avg,
                           rounds=2, **faults)
    streams = obs.streams_of(m)
    assert "params" in streams
    assert set(m) == set(obs.round_metric_keys(streams))
    # runtime wire identities mirror the static accounting
    split = sum(int(m[f"wire_bytes/{s}"]) for s in streams)
    assert int(m["wire_bytes"]) == split
    if ex.p2p:
        assert int(m["wire_bytes"]) == int(m["wire_bytes_up"]) \
            == int(m["wire_bytes_down"])
    else:
        assert int(m["wire_bytes"]) == (int(m["wire_bytes_up"])
                                        + int(m["wire_bytes_down"]))
    # uniform defaults where the feature is off
    assert 0.0 <= float(m["participation"]) <= 1.0
    assert float(m["delivery_rate"]) == pytest.approx(ex.delivery_rate)
    if not faults:
        assert float(m["participation"]) == 1.0
    if topo != "push_sum":
        assert float(m["backlog_mass"]) == 0.0
    # consensus distance: (G,) nonnegative, and the exchange tightened it
    pre = np.asarray(m["consensus_sq"])
    post = np.asarray(m["consensus_sq_post"])
    assert pre.shape == (G,) and post.shape == (G,)
    assert np.all(pre >= 0) and np.all(post >= 0)
    # codec error mass: zero unless the codec keeps an EF residual
    err = np.asarray(m["codec_err/params"])
    assert err.shape == (G,) and np.all(err >= 0)
    if codec != "topk":
        assert np.all(err == 0)


def test_consensus_metric_tracks_drift_and_mixing(key):
    """server/fp32: the post-exchange consensus distance is ~0 (exact
    mean), the pre-exchange one is positive (groups drifted during local
    steps on different data)."""
    _, _, m = _run_round(key, "server", "fp32")
    assert float(np.max(m["consensus_sq"])) > 0
    assert float(np.max(m["consensus_sq_post"])) \
        <= 1e-10 * max(1.0, float(np.max(m["consensus_sq"])))


def test_topk_codec_err_reports_residual_mass(key):
    """topk error feedback: the round's codec_err/params equals the
    squared mass actually held in the EF residual state."""
    _, st, m = _run_round(key, "ring", "topk", rounds=2)
    res = st["comm"]["codec"]["params"]["residual"]
    want = np.sum(np.square(np.asarray(res, np.float64)),
                  axis=tuple(range(1, np.ndim(res))))
    np.testing.assert_allclose(np.asarray(m["codec_err/params"]), want,
                               rtol=1e-5)
    assert float(np.max(want)) > 0      # topk actually deferred mass


def test_pytree_round_emits_same_schema(key):
    """The per-leaf pytree engine (no layout) emits the identical
    uniform schema — per-stream keys for params + averaged moments."""
    _, _, m = _run_round(key, "server", "fp32", opt_name="adamw",
                         packed=False, avg=True)
    streams = obs.streams_of(m)
    assert set(streams) == {"params", "m", "v"}
    # the pytree engine keeps its per-step trajectory extras; the uniform
    # contract is that every obs key is PRESENT, not that nothing else is
    assert set(obs.round_metric_keys(streams)) <= set(m)


# ---------------------------------------------------------------------------
# trace round-trip (host-side layer + report)
# ---------------------------------------------------------------------------

def test_trace_roundtrip_faulty_push_sum(key, tmp_path):
    """Write a trace from a short faulty push_sum run through the real
    Trace.phase/emit_round path, re-read it with obs.report: --check
    clean, monotone rounds, phase durations present, consensus/
    participation summarized."""
    path = tmp_path / "run.jsonl"
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("sgd", 0.05, impl="jnp")
    ex = comm.get_exchange("push_sum", "fp32", G, drop_rate=0.2,
                           fault_seed=5)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    with obs.Trace(str(path), meta={"comm": ex.name, "groups": G}) as tr:
        for n in range(4):
            with tr.phase("round") as f:
                st, m = f(rnd(st, batch))
            tr.emit_round(n, {**m, **rnd.wire_bytes(st)})
    meta, records = report.load(path)
    assert report.check(meta, records) == []
    assert meta["schema"] == obs.SCHEMA_VERSION
    assert meta["comm"] == ex.name
    rounds = report.rounds_of(records)
    assert [r["round"] for r in rounds] == [0, 1, 2, 3]
    for r in rounds:
        assert r["phase_s"]["round"] >= 0.0
        assert set(obs.round_metric_keys(("params",))) <= set(r["metrics"])
    s = report.summarize(meta, records)
    assert s["n_rounds"] == 4
    assert len(s["consensus_sq"]["trajectory"]) == 4
    assert 0.0 < s["participation"]["min"] <= 1.0
    assert s["wire_bytes_total"] == 4 * int(rounds[0]["metrics"]
                                            ["wire_bytes"])
    # CLI --check exits 0 on this file
    assert report.main([str(path), "--check"]) == 0


def test_report_check_flags_broken_traces(tmp_path):
    """--check catches: missing meta, non-monotone rounds, missing
    schema keys, split/total mismatch."""
    m_ok = {k: 1.0 for k in obs.round_metric_keys(("params",))}
    m_ok.update({"wire_bytes": 8, "wire_bytes_up": 8, "wire_bytes_down": 8,
                 "wire_bytes/params": 8, "participation": 1.0})

    def write(path, lines):
        path.write_text("\n".join(json.dumps(x) for x in lines) + "\n")
        return report.check(*report.load(path))

    meta = {"kind": "meta", "schema": obs.SCHEMA_VERSION}
    rec = {"kind": "round", "round": 0, "phase_s": {"round": 0.1},
           "metrics": m_ok}
    p = tmp_path / "t.jsonl"
    assert write(p, [meta, rec]) == []
    assert any("meta" in s for s in write(p, [rec]))
    assert any("monotone" in s for s in write(
        p, [meta, rec, dict(rec, round=0)]))
    bad_keys = dict(rec, metrics={"loss": 1.0})
    assert any("missing metric keys" in s
               for s in write(p, [meta, bad_keys]))
    bad_split = dict(rec, metrics=dict(m_ok, wire_bytes=999))
    assert any("per-stream splits" in s
               for s in write(p, [meta, bad_split]))


def test_full_width_round_wire_counts_stay_exact(tmp_path):
    """paper-lenet at full width, G=4, momentum: one round moves
    7,978,401,792 wire bytes — past int32, where tracing the round used
    to die with an OverflowError. The round traces (abstractly, nothing
    allocated), its host-side counts are exact ints, and a record
    carrying them passes report --check."""
    from repro.configs.base import get_config
    from repro.models import build_model

    model = build_model(get_config("paper-lenet"), schedule="rect")
    pabs = model.abstract()
    layout = packing.layout_of(pabs)
    opt = optim.packed("momentum", 0.05, impl="jnp")
    ex = comm.get_exchange("server", "fp32", G)
    rnd = lsgd.make_local_round(
        model.loss, opt, lsgd.LocalSGDConfig(n_groups=G, inner_steps=1),
        layout=layout, exchange=ex)
    st = jax.eval_shape(lambda p: lsgd.init_state(
        p, opt, n_groups=G, layout=layout, exchange=ex), pabs)
    batch = {"tokens": jax.ShapeDtypeStruct((G, 1, 8), jnp.int32)}
    _, m = jax.eval_shape(rnd, st, batch)
    assert not any(k.startswith("wire_bytes") for k in m)
    wire = rnd.wire_bytes(st)
    # server fp32: G pushes + G replies of params + mu, 4 bytes each
    assert wire["wire_bytes"] == 2 * G * 4 * 2 * layout.size \
        == 7_978_401_792
    rec = {k: 1.0 for k in obs.round_metric_keys(("params", "mu"))}
    rec.update(wire)
    path = tmp_path / "full.jsonl"
    with obs.Trace(str(path), meta={"arch": "paper-lenet"}) as tr:
        with tr.phase("round"):
            pass
        tr.emit_round(0, rec)
    assert report.check(*report.load(path)) == []


def test_trace_null_sink_still_times(key):
    """Trace(path=None): no file I/O, but phases still fence and time —
    the launchers run one code path whether or not --trace is set."""
    tr = obs.Trace(None)
    x = jnp.zeros((256, 256))
    with tr.phase("round") as f:
        y = f(x @ x)
    rec = tr.emit_round(0, {"loss": y[0, 0]})
    assert rec["phase_s"]["round"] >= 0.0
    assert tr.n_records == 1
    tr.close()


def test_phase_timer_fences_async_dispatch():
    """The satellite-1 fix in microcosm: an unfenced delta around a
    dispatched matmul chain reads ~0; the fenced PhaseTimer waits for
    the value. (Asserting fenced >= unfenced, not absolute times —
    container clocks are noisy.)"""
    import time
    x = jnp.ones((512, 512))

    @jax.jit
    def chain(x):
        for _ in range(8):
            x = x @ x / 512.0
        return x

    chain(x).block_until_ready()          # compile outside the timers
    t0 = time.perf_counter()
    y = chain(x)
    unfenced = time.perf_counter() - t0
    with obs.PhaseTimer() as t:
        t.fence(chain(y))
    assert t.seconds >= 0.0
    jax.block_until_ready(y)
    assert unfenced >= 0.0                # smoke: both paths executed


# ---------------------------------------------------------------------------
# consensus parity replicated vs sharded (forced-8-device mesh)
# ---------------------------------------------------------------------------

def mesh8(shape=(4, 2), axes=("data", "model")):
    from jax.sharding import Mesh
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


@needs8
def test_consensus_sq_groups_matches_flat_reference(key):
    """shardexec.consensus_sq_groups (pmean over groups + shard-local
    sq + psum over shards) against the replicated flat reduction on the
    same (G, Np) buffer: <= 1e-5 rel."""
    from repro.core.localsgd import _consensus_sq_flat
    from repro.sharding import shardexec as shx

    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    x = packing.pack(lsgd.replicate(params, G), layout)
    x = x + jax.random.normal(key, x.shape) * 0.1
    got = jax.jit(sexec.consensus_sq_groups(use_pallas=False))(x)
    want = jax.jit(lambda b: _consensus_sq_flat(b, False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)
    assert float(np.min(want)) > 0


@needs8
def test_consensus_trajectory_parity_replicated_vs_sharded(key, tmp_path):
    """ISSUE 7 acceptance: trace a short faulty push_sum run on the
    replicated AND the sharded packed engine — the per-round consensus
    trajectories agree <= 1e-5 everywhere in the two trace files."""
    from repro.sharding import shardexec as shx

    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, batch = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    ex = comm.get_exchange("push_sum", "fp32", G, drop_rate=0.05,
                           fault_seed=2)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    traces = {}
    for tag, sx in (("replicated", None), ("sharded", sexec)):
        opt = optim.packed("sgd", 0.05, impl="jnp")
        rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                            layout=layout, exchange=ex,
                                            shardexec=sx))
        st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                             exchange=ex)
        path = tmp_path / f"{tag}.jsonl"
        with obs.Trace(str(path), meta={"engine": tag}) as tr:
            for n in range(4):
                with tr.phase("round") as f:
                    st, m = f(rnd(st, batch))
                tr.emit_round(n, {**m, **rnd.wire_bytes(st)})
        meta, records = report.load(path)
        assert report.check(meta, records) == []
        traces[tag] = report.summarize(meta, records)
    for k in ("consensus_sq",):
        a = np.asarray(traces["replicated"][k]["trajectory"])
        b = np.asarray(traces["sharded"][k]["trajectory"])
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-12)
    assert traces["replicated"]["participation"]["min"] \
        == pytest.approx(traces["sharded"]["participation"]["min"])


# ---------------------------------------------------------------------------
# tier-1 driver: force 8 host devices in a child process
# ---------------------------------------------------------------------------

def test_suite_under_forced_8_devices():
    """Under the plain 1-device tier-1 run, re-run this module with 8
    forced host devices in a subprocess (jax locks the device count at
    first init). CI's forced-8-device job runs the tests directly and
    skips this driver."""
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
        f"8-device obs suite failed:\n{r.stdout[-4000:]}"
        f"\n{r.stderr[-2000:]}")
