"""Overlapped exchange + online T (ISSUE 8 / DESIGN.md §14).

Acceptance-critical invariants:
  * overlap=off IS the PR-7 engine: the flag defaults off, allocates no
    in-flight buffer, and leaves the barrier round bit-identical,
  * the overlap round implements delayed mixing exactly — a hand-rolled
    local-then-correct reference (p' = Local(p) + mix(inflight) −
    inflight, inflight' = p') reproduces the engine bit-for-bit on the
    identity codec, and a uniform start makes round 0 a pure local
    round,
  * the refusal matrix is enforced up front: overlap composes with
    server/ring/gossip × {fp32, fp16, bf16, int8, int8z} and REFUSES
    none/async_stale/push_sum, downlink re-encodes, multi-hop mixing,
    fault injection, top-k EF, and the unpacked pytree path,
  * delayed mixing still converges (the one-round lag is bounded
    staleness s=1): the convex suite reaches its gsq floor on every
    supported topology × codec cell,
  * the in-flight payload checkpoint-round-trips bit-exactly and the
    resumed run continues bit-identically to the uninterrupted one,
  * int8z (DESIGN.md §10 caveat closure) preserves exact zeros, prices
    the same wire bytes as int8, keeps jnp/pallas bit-parity, and holds
    the adamw moment streams through a lossy exchange,
  * OnlineT steers T from measured telemetry: the consensus guard
    shrinks T under weak mixing, convergence relief ramps it as
    consensus collapses, and missing signals degrade gracefully,
  * obs.exchange_phases / report gates: exposed ≤ total, the pair
    appears together, and an overlap run without the split is flagged.

8-device cells ride the same forced-host child-process pattern as
tests/test_shardexec.py (REPRO_SHARDEXEC_CHILD gates the in-suite
driver so CI's dedicated 8-device job doesn't pay twice).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, obs, optim
from repro.comm import codecs
from repro.core import controller, localsgd as lsgd
from repro.obs import report
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


def _packed_round(key, topology, codec, *, opt_name="sgd", lr=0.3,
                  inner=4, overlap=True, moment_codec="fp32",
                  impl="jnp", shardexec=None, d=40):
    params, batch = make_problem(key, d=d)
    layout = packing.layout_of(params)
    if shardexec is not None:
        layout = packing.shard_layout(layout, shardexec.n_shards)
    opt = optim.packed(opt_name, lr, impl=impl)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=inner)
    ex = comm.get_exchange(topology, codec, G, overlap=overlap,
                           moment_codec=moment_codec, impl=impl)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex,
                                        shardexec=shardexec))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    return rnd, st, batch, ex, layout


# ---------------------------------------------------------------------------
# overlap=off is the engine default (no behavior drift)
# ---------------------------------------------------------------------------


def test_overlap_defaults_off_and_changes_nothing(key):
    """The flag defaults off; an explicit overlap=False exchange runs
    bit-identically to the default-constructed one and allocates no
    in-flight buffer — the PR-7 barrier engine is untouched."""
    ex_def = comm.get_exchange("ring", "int8", G)
    assert ex_def.overlap is False
    assert "+ov" not in ex_def.name
    rnd_a, st_a, batch, _, _ = _packed_round(key, "ring", "int8",
                                             overlap=False)
    ex_off = comm.get_exchange("ring", "int8", G, overlap=False)
    assert "inflight" not in ex_off.init(st_a["params"])
    rnd_b, st_b, _, _, _ = _packed_round(key, "ring", "int8",
                                         overlap=False)
    for _ in range(3):
        st_a, ma = rnd_a(st_a, batch)
        st_b, mb = rnd_b(st_b, batch)
    np.testing.assert_array_equal(np.asarray(st_a["params"]),
                                  np.asarray(st_b["params"]))
    np.testing.assert_array_equal(np.asarray(ma["grad_sq"]),
                                  np.asarray(mb["grad_sq"]))


def test_overlap_names_and_inflight_state(key):
    """overlap=True tags the exchange name, and init_state allocates
    comm['inflight'] per stream, seeded with the start point (a uniform
    start → the first correction is exactly zero)."""
    params, _ = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("sgd", 0.3, impl="jnp")
    ex = comm.get_exchange("server", "fp32", G, overlap=True)
    assert "+ov" in ex.name
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    inf = st["comm"]["inflight"]
    assert set(inf) == {"params"}
    np.testing.assert_array_equal(np.asarray(inf["params"]),
                                  np.asarray(st["params"]))


# ---------------------------------------------------------------------------
# refusal matrix (DESIGN.md §14)
# ---------------------------------------------------------------------------


def test_overlap_refusal_matrix():
    """Every cell the §14 matrix refuses raises up front, with the
    valid alternatives named."""
    for topo in ("none", "async_stale", "push_sum"):
        with pytest.raises(NotImplementedError, match="overlap"):
            comm.get_exchange(topo, "fp32", G, overlap=True)
    with pytest.raises(NotImplementedError, match="downlink"):
        comm.get_exchange("server", "fp32", G, overlap=True,
                          downlink_codec="int8")
    for topo in ("ring", "gossip"):
        with pytest.raises(NotImplementedError, match="mix_rounds"):
            comm.get_exchange(topo, "fp32", G, overlap=True,
                              mix_rounds=2)
    with pytest.raises(NotImplementedError, match="fault"):
        comm.get_exchange("server", "fp32", G, overlap=True,
                          drop_rate=0.1)
    with pytest.raises(NotImplementedError, match="fault"):
        comm.get_exchange("ring", "fp32", G, overlap=True,
                          stall_rate=0.1)
    with pytest.raises(NotImplementedError, match="fault"):
        comm.get_exchange("server", "fp32", G, overlap=True,
                          dropouts=((1, 0, 2),))
    # top-k EF re-offers against a one-round-stale reference: loop gain
    # > 1 at small fractions, measured divergent — refused, not fixed
    with pytest.raises(NotImplementedError, match="topk"):
        comm.get_exchange("server", "topk", G, overlap=True)
    with pytest.raises(NotImplementedError, match="topk"):
        comm.get_exchange("server", "fp32", G, overlap=True,
                          moment_codec="topk")


def test_overlap_needs_packed_layout(key):
    """The in-flight payload is a flat stream buffer — the pytree path
    has nowhere to put it and the round builder says so."""
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "fp32", G, overlap=True)
    with pytest.raises(NotImplementedError, match="inflight"):
        lsgd.make_local_round(quad_loss, optim.sgd(0.1), cfg,
                              exchange=ex)


# ---------------------------------------------------------------------------
# delayed-mixing semantics
# ---------------------------------------------------------------------------


def test_round0_uniform_start_is_pure_local(key):
    """All groups start at the same point, so the seeded in-flight
    payload is uniform, mix(inflight) == inflight, and round 0 of the
    overlap engine is bit-identical to a communication-free round."""
    rnd_ov, st_ov, batch, _, _ = _packed_round(key, "server", "fp32")
    rnd_no, st_no, _, _, _ = _packed_round(key, "none", "fp32",
                                           overlap=False)
    st_ov, _ = rnd_ov(st_ov, batch)
    st_no, _ = rnd_no(st_no, batch)
    np.testing.assert_array_equal(np.asarray(st_ov["params"]),
                                  np.asarray(st_no["params"]))


def test_delayed_mixing_matches_handrolled_reference(key):
    """THE §14 semantics gate: on the identity codec the engine's round
    is exactly p' = Local(p) + mix(inflight) − inflight with
    inflight' = p'. A hand-rolled reference that runs the engine's own
    communication-free round for Local(.) and applies the correction by
    hand reproduces the overlap engine bit-for-bit across rounds, for
    the server mean and the ring W alike."""
    for topo in ("server", "ring"):
        rnd_ov, st_ov, batch, ex, _ = _packed_round(key, topo, "fp32")
        rnd_none, st_no, _, _, _ = _packed_round(key, "none", "fp32",
                                                 overlap=False)
        # reference state: same packed buffers, no comm['inflight']
        st_ref = {"params": st_no["params"], "opt": st_no["opt"]}
        inflight = np.asarray(st_ov["comm"]["inflight"]["params"])
        mix = jax.jit(ex.mix)
        for _ in range(4):
            st_ov, _ = rnd_ov(st_ov, batch)
            # Local(p): the none-topology round on the reference state
            loc = {"params": st_ref["params"], "opt": st_ref["opt"]}
            loc, _ = rnd_none(loc, batch)
            corrected = np.asarray(loc["params"]) + (
                np.asarray(mix(jnp.asarray(inflight))) - inflight)
            st_ref = {"params": jnp.asarray(corrected), "opt": loc["opt"]}
            inflight = corrected          # identity codec ships p' itself
            np.testing.assert_array_equal(
                np.asarray(st_ov["params"]), corrected)
            np.testing.assert_array_equal(
                np.asarray(st_ov["comm"]["inflight"]["params"]),
                inflight)


@pytest.mark.parametrize("topology,codec", [
    ("server", "fp32"), ("server", "int8"), ("server", "int8z"),
    ("ring", "int8z"), ("ring", "bf16"), ("gossip", "fp32"),
])
def test_overlap_convergence_matrix(key, topology, codec):
    """Delayed mixing is bounded staleness s=1 — it converges on every
    supported topology × codec cell of the convex suite (the lag shifts
    WHEN consensus contraction lands, not whether)."""
    rnd, st, batch, _, _ = _packed_round(key, topology, codec)
    for _ in range(200):
        st, m = rnd(st, batch)
    gsq = float(jnp.mean(m["grad_sq"]))
    # the over-parameterized instance sits in the paper's sublinear
    # regime — the barrier engine measures ~5e-4 at 200 rounds here and
    # overlap tracks it (4.7–4.9e-4 across the matrix); 2e-3 is a 4x
    # margin, not a loose bound
    assert gsq < 2e-3, (topology, codec, gsq)
    assert float(jnp.mean(m["consensus_sq_post"])) < 2e-2


def test_overlap_tracks_async_stale_s1(key):
    """The documented equivalence (DESIGN.md §14): delayed mixing IS
    bounded staleness s=1 applied on every topology — both reach the
    convex-suite floor; neither stalls the other's trajectory by more
    than the staleness lag's transient."""
    rnd_ov, st_ov, batch, _, _ = _packed_round(key, "server", "fp32")
    rnd_as, st_as, _, _, _ = _packed_round(key, "async_stale", "fp32",
                                           overlap=False)
    for _ in range(200):
        st_ov, m_ov = rnd_ov(st_ov, batch)
        st_as, m_as = rnd_as(st_as, batch)
    g_ov = float(jnp.mean(m_ov["grad_sq"]))
    g_as = float(jnp.mean(m_as["grad_sq"]))
    assert g_ov < 2e-3 and g_as < 5e-3, (g_ov, g_as)
    # the lag costs at most a small constant factor, not the rate
    assert g_ov < 10 * g_as + 1e-9


# ---------------------------------------------------------------------------
# in-flight payload: checkpoint round trip mid-overlap
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topology", ["server", "ring"])
@pytest.mark.parametrize("codec", ["fp32", "int8"])
def test_inflight_checkpoint_roundtrip(key, tmp_path, topology, codec):
    """The in-flight payload (and its codec counters) survives a
    checkpoint round trip bit-exactly MID-OVERLAP, and the resumed run
    continues bit-identically to the uninterrupted one — same contract
    as the §10/§11 stream states."""
    from repro.checkpoint import io as ckpt_io

    rnd, st, batch, _, _ = _packed_round(key, topology, codec)
    for _ in range(2):
        st, _ = rnd(st, batch)
    assert "inflight" in st["comm"]
    path = str(tmp_path / f"ck_{topology}_{codec}")
    ckpt_io.save(path, st, metadata={})
    back = ckpt_io.load(path, st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for _ in range(2):
        back, mb = rnd(back, batch)
        st, mc = rnd(st, batch)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(mc["grad_sq"]),
                                  np.asarray(mb["grad_sq"]))


# ---------------------------------------------------------------------------
# int8z: the moment-friendly zero-preserving codec (§10 caveat closure)
# ---------------------------------------------------------------------------


def test_int8z_preserves_dying_coordinates(key):
    """Sub-half-quantum elements — a dead coordinate's exponentially
    decaying moment mass — decode to EXACT zero (deterministic floor),
    while at/above half a quantum the codec keeps int8's
    stochastic-rounding semantics. Plain int8's unbiased dither kicks
    the same near-zero elements a FULL quantum off zero — exactly the
    §10 moment caveat (a quantum of m over v̂ ≈ 0 is a 1/eps-size
    step)."""
    c = codecs.get_codec("int8z", impl="jnp")
    delta = jax.random.normal(key, (G, 512))
    dead = (jnp.arange(512) % 3 == 0)
    # ~0.3 of a quantum: chunk amax ~ 3sigma so the quantum is ~0.025;
    # int8's floor(x/s + u) then kicks each dead element to a FULL
    # quantum with probability x/s ~ 0.3 — while int8z's deterministic
    # sub-half-quantum floor takes all of them to exact zero
    tiny = 8e-3
    delta = jnp.where(dead[None, :], tiny, delta)
    d_hat, _ = c.compress(delta, c.init(delta))
    np.testing.assert_array_equal(
        np.asarray(d_hat)[:, np.asarray(dead)], 0.0)
    # the live coordinates still carry mass (not zeroed wholesale)
    assert float(jnp.sum(jnp.abs(d_hat))) > 0.0
    # exact zeros are preserved too (floor(0 + u) == 0 for u < 1)
    z_hat, _ = c.compress(jnp.zeros_like(delta), c.init(delta))
    np.testing.assert_array_equal(np.asarray(z_hat), 0.0)
    # plain int8's dither kicks sub-half-quantum mass off zero — the
    # caveat int8z closes
    c8 = codecs.get_codec("int8", impl="jnp")
    d8, _ = c8.compress(delta, c8.init(delta))
    assert float(np.abs(np.asarray(d8)[:, np.asarray(dead)]).max()) > 0.0


def test_int8z_same_wire_bytes_and_impl_parity(key):
    """int8z prices exactly int8's wire (1 B/elem + fp32 chunk scales)
    and the pallas and jnp impls agree bit-for-bit (the zero mask is
    computed before the shared qdq core consumes the shared noise)."""
    n = 4096
    cz = codecs.get_codec("int8z", impl="jnp")
    c8 = codecs.get_codec("int8", impl="jnp")
    assert cz.wire_bytes(n) == c8.wire_bytes(n)
    ez = comm.get_exchange("server", "int8z", G)
    e8 = comm.get_exchange("server", "int8", G)
    assert ez.wire_bytes_per_round(n) == e8.wire_bytes_per_round(n)
    delta = jax.random.normal(key, (G, 1024)) * \
        (jnp.arange(1024) % 5 != 0)[None, :]
    cp = codecs.get_codec("int8z", impl="pallas")
    dj, _ = cz.compress(delta, cz.init(delta))
    dp, _ = cp.compress(delta, cp.init(delta))
    np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp))


def test_int8z_holds_adamw_moments(key):
    """The §10 caveat closure at convergence scale: adamw with int8z
    moment streams converges on the convex suite and the second moment
    stays non-negative — dead coordinates' v stays EXACTLY dead instead
    of receiving a full-quantum kick over v̂ ≈ 0."""
    rnd, st, batch, ex, _ = _packed_round(
        key, "server", "fp32", opt_name="adamw", lr=0.05,
        moment_codec="int8z", overlap=False)
    for _ in range(200):
        st, m = rnd(st, batch)
    assert float(jnp.mean(m["grad_sq"])) < 1e-4      # measured 1.2e-5
    assert float(jnp.min(st["opt"]["v"])) >= 0.0
    # the moment wire is priced as int8 (codec_err reported per stream)
    assert "codec_err/v" in m and "codec_err/m" in m


def test_int8z_overlap_round(key):
    """int8z composes with overlap (the refusal matrix admits it where
    int8 is admitted), the moment streams ride the in-flight buffer, and
    the combined round makes progress. HONEST FLOOR: the adamw
    preconditioner riding the delayed additive correction converges
    measurably slower than the barrier round (DESIGN.md §14) — the gate
    here is monotone progress plus a coarse floor, not the barrier's."""
    rnd, st, batch, _, _ = _packed_round(key, "server", "int8z",
                                         opt_name="adamw", lr=0.05,
                                         moment_codec="int8z")
    st, m0 = rnd(st, batch)
    g0 = float(jnp.mean(m0["grad_sq"]))
    for _ in range(200):
        st, m = rnd(st, batch)
    gsq = float(jnp.mean(m["grad_sq"]))
    assert gsq < 1e-1 and gsq < g0 / 3, (gsq, g0)    # measured 2.3e-2
    assert float(jnp.min(st["opt"]["v"])) >= 0.0
    assert set(st["comm"]["inflight"]) == {"params", "m", "v"}


# ---------------------------------------------------------------------------
# OnlineT controller
# ---------------------------------------------------------------------------

TRAJ = 10.0 * 0.5 ** np.arange(8)      # clean geometric local decay


def test_onlinet_measures_cost_ratio():
    """The fenced phase times move r̂: cheap local steps relative to the
    exchange (small r) pull T* down; with no timing the prior holds."""
    c = controller.OnlineT(r=1.0, r_ema=0.0)      # no smoothing: track
    c.update(TRAJ, t_used=4, local_s=0.4, exchange_s=0.01)
    assert c.r == pytest.approx((0.4 / 4) / 0.01)  # = 10
    r_before = c.r
    c.update(TRAJ, t_used=4)                       # no timing signal
    assert c.r == r_before


def test_onlinet_consensus_guard_shrinks_t():
    """Weak mixing (consensus barely contracts, codec error mass rides
    on top) drives γ̂ up and scales the target T down vs a strong-mixing
    twin fed the same decay trajectory."""
    weak = controller.OnlineT(guard_ema=0.0, ema=0.0)
    strong = controller.OnlineT(guard_ema=0.0, ema=0.0)
    weak.update(TRAJ, t_used=4, consensus_pre=1.0,
                consensus_post=0.9, codec_err=0.2)
    strong.update(TRAJ, t_used=4, consensus_pre=1.0,
                  consensus_post=0.01)
    assert weak._gamma == pytest.approx(0.95)      # clipped
    assert strong._gamma == pytest.approx(0.01)
    # the raw EMA state carries the scaling even when both clip to the
    # same integer T at this trajectory's small T*
    assert weak._t < strong._t
    assert weak._t == pytest.approx(strong._t * (1 - 0.95) / (1 - 0.01))


def test_onlinet_convergence_relief_ramps_t():
    """As consensus mass collapses below its initial c₀ the relief
    factor sqrt(c₀/pre) ramps T (capped at relief_max) — fewer, longer
    rounds at the tail is where online-T saves wire."""
    c = controller.OnlineT(ema=0.0, guard_ema=0.0)
    c.update(TRAJ, t_used=4, consensus_pre=1.0, consensus_post=1e-4)
    t_early = c.t
    c.update(TRAJ, t_used=4, consensus_pre=1e-4, consensus_post=1e-8)
    t_late = c.t
    assert t_late > t_early
    assert c.history[-1]["relief"] <= c.relief_max
    c.update(TRAJ, t_used=4, consensus_pre=1e-12, consensus_post=0.0)
    assert c.history[-1]["relief"] == pytest.approx(c.relief_max)


def test_onlinet_divergence_guard_clamps_at_stability_edge():
    """The lr·T guard (DESIGN.md §14): consensus mass that GROWS between
    exchanges at a measured per-step exponent â, against mixing that
    only retires 1-γ̂ of it, is stable only for T < ln(1/γ̂)/â. The
    guarded controller clamps there; a clamp-disabled twin fed the
    SAME telemetry keeps T high (the multiplicative (1-γ̂) factor slows
    growth but does not bound T)."""
    guarded = controller.OnlineT(r=0.001, _t=10.0)
    loose = controller.OnlineT(r=0.001, _t=10.0, guard_margin=1e9)
    for ctl in (guarded, loose):
        c_post, t = 1.0, 10
        for _ in range(10):
            c_pre = c_post * np.exp(0.4 * t)     # drift: a = 0.4 / step
            c_post = 0.6 * c_pre                 # weak mixing: γ = 0.6
            t = ctl.update(TRAJ, t_used=t, consensus_pre=c_pre,
                           consensus_post=c_post)
    h = guarded.history[-1]
    assert h["a"] == pytest.approx(0.4, rel=0.1)
    assert h["t_guard"] is not None
    # analytic edge: 0.5 * ln(1/0.6) / 0.4 ~ 0.64 -> clamps to t_min
    assert guarded.t == guarded.t_min
    # same telemetry, clamp disabled: the (1-γ̂) factor leaves T at
    # ~0.4 * t_cost, well ABOVE the stability edge
    assert loose.t >= 3 * guarded.t
    assert loose.history[-1]["t_guard"] is not None  # computed, unbinding


def test_onlinet_guard_bounds_the_measured_divergent_config(key):
    """THE §14 caveat, lifted from docs-only to a controller guarantee:
    on the fully-determined quadratic (r=24, d=32) at lr 0.3,
    overlapped decentralized ring at static T=6 DIVERGES (consensus
    mass compounds round over round — the measured caveat), while the
    SAME config with the online controller's divergence guard driving T
    stays bounded and converges."""
    params, batch = make_problem(key, r=24, d=32)
    layout = packing.layout_of(params)
    opt = optim.packed("sgd", 0.3, impl="jnp")
    ex = comm.get_exchange("ring", "fp32", G, overlap=True, impl="jnp")
    rounds_cache = {}

    def round_for(t):
        if t not in rounds_cache:
            cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t,
                                      metrics="traj")
            rounds_cache[t] = jax.jit(lsgd.make_local_round(
                quad_loss, opt, cfg, layout=layout, exchange=ex))
        return rounds_cache[t]

    def drive(ctl, rounds=30):
        st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                             exchange=ex)
        t_cur, cons = 6, []
        for _ in range(rounds):
            st, m = round_for(t_cur)(st, batch)
            pre = float(jnp.mean(m["consensus_sq"]))
            cons.append(pre)
            if not np.isfinite(pre) or pre > 1e6:
                break
            if ctl is not None:
                t_cur = ctl.update(
                    np.asarray(m["grad_sq_traj"])[0],
                    t_used=int(jnp.max(m["inner_steps"])),
                    consensus_pre=pre,
                    consensus_post=float(
                        jnp.mean(m["consensus_sq_post"])))
        return cons

    static = drive(None)                 # the documented caveat: T fixed
    ctl = controller.OnlineT(r=1.0, _t=6.0)
    guarded = drive(ctl)
    assert static[-1] > 5 * static[0], static[-1]      # compounding
    assert guarded[-1] < 0.1, guarded[-1]              # converged
    assert static[-1] > 100 * guarded[-1]
    assert max(guarded) < 100 * guarded[0]
    # the guard actually engaged (not just the γ̂ scaling)
    assert any(h["t_guard"] is not None for h in ctl.history)


def test_onlinet_degrades_gracefully():
    """No telemetry at all reduces OnlineT to AdaptiveT with the prior
    r: same fitted T* core, no crash, T stays in [t_min, t_max]."""
    on = controller.OnlineT(r=2.0)
    ad = controller.AdaptiveT(r=2.0)
    for _ in range(3):
        t_on = on.update(TRAJ, t_used=4)
        t_ad = ad.update(TRAJ)
    assert t_on == t_ad
    # degenerate trajectory: fit fails, T holds its EMA state
    t_before = on.t
    assert on.update(np.ones(2), t_used=4) == t_before
    assert on.t_min <= on.t <= on.t_max


# ---------------------------------------------------------------------------
# phase fences + report gates
# ---------------------------------------------------------------------------


def test_exchange_phases_math():
    """exposed = round − local reference (floored at 0); total is the
    standalone exchange cost for overlap rounds (floored at exposed) and
    == exposed for barrier rounds, so barrier efficiency is exactly 0."""
    f = obs.exchange_phases(0.5, 0.4, 0.3, overlap=True)
    assert f["exchange_exposed"] == pytest.approx(0.1)
    assert f["exchange_total"] == pytest.approx(0.3)
    f = obs.exchange_phases(0.9, 0.4, 0.3, overlap=True)
    assert f["exchange_total"] == pytest.approx(0.5)   # floored at exposed
    f = obs.exchange_phases(0.5, 0.4, 0.0, overlap=False)
    assert f["exchange_exposed"] == f["exchange_total"]
    f = obs.exchange_phases(0.1, 0.4, 0.0, overlap=False)
    assert f["exchange_exposed"] == 0.0                # never negative


def _trace_records(phase_s, meta_extra=()):
    m = {k: 1.0 for k in obs.round_metric_keys(("params",))}
    m.update({"wire_bytes": 8, "wire_bytes_up": 8, "wire_bytes_down": 8,
              "wire_bytes/params": 8, "participation": 1.0})
    meta = {"kind": "meta", "schema": obs.SCHEMA_VERSION}
    meta.update(dict(meta_extra))
    rec = {"kind": "round", "round": 0, "phase_s": dict(phase_s),
           "metrics": m}
    return meta, [rec]


def test_report_gates_exchange_phase_pair():
    """--check: the exposed/total pair must appear together, exposed may
    not exceed total, and an overlap-meta run without the split is a
    schema problem (the overlap win would be unmeasured)."""
    ok = {"round": 0.1, "exchange_exposed": 0.02, "exchange_total": 0.05}
    assert report.check(*_trace_records(ok)) == []
    lone = {"round": 0.1, "exchange_exposed": 0.02}
    assert any("together" in s for s in report.check(*_trace_records(lone)))
    flipped = {"round": 0.1, "exchange_exposed": 0.9,
               "exchange_total": 0.1}
    assert any("exchange_total" in s
               for s in report.check(*_trace_records(flipped)))
    bare = {"round": 0.1}
    assert report.check(*_trace_records(bare)) == []
    assert any("unmeasured" in s for s in report.check(
        *_trace_records(bare, meta_extra={"overlap": True})))


def test_report_summarize_overlap_efficiency(tmp_path):
    """summarize() exposes overlap efficiency = 1 − Σexposed/Σtotal; a
    barrier trace (exposed == total) reports exactly 0."""
    meta, recs = _trace_records(
        {"round": 0.1, "exchange_exposed": 0.02, "exchange_total": 0.08})
    s = report.summarize(meta, recs)
    assert s["overlap_efficiency"] == pytest.approx(0.75)
    meta, recs = _trace_records(
        {"round": 0.1, "exchange_exposed": 0.05, "exchange_total": 0.05})
    assert report.summarize(meta, recs)["overlap_efficiency"] == 0.0
    meta, recs = _trace_records({"round": 0.1})
    assert "overlap_efficiency" not in report.summarize(meta, recs)
    path = tmp_path / "t.jsonl"
    m, r = _trace_records(
        {"round": 0.1, "exchange_exposed": 0.02, "exchange_total": 0.08},
        meta_extra={"overlap": True})
    path.write_text("\n".join(json.dumps(x) for x in [m] + r) + "\n")
    assert report.main([str(path), "--check"]) == 0
    assert report.main([str(path)]) == 0


# ---------------------------------------------------------------------------
# 8-device mesh: sharded overlap parity
# ---------------------------------------------------------------------------


@needs8
@pytest.mark.parametrize("topology,codec", [("server", "int8"),
                                            ("ring", "int8z")])
def test_sharded_overlap_matches_replicated(topology, codec, key):
    """The shard_map overlap round (encode+permute issued before the
    packed local-step block, in-flight buffer sharded like its stream)
    tracks the replicated overlap round within the engine's reduction-
    order tolerance, with identical wire accounting."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    rnd_s, st_s, batch, _, layout = _packed_round(
        key, topology, codec, shardexec=sexec)
    # the replicated twin runs on the SAME padded layout the shards use
    params, _ = make_problem(key)
    opt = optim.packed("sgd", 0.3, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4)
    ex = comm.get_exchange(topology, codec, G, overlap=True, impl="jnp")
    rnd_r = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                          layout=layout, exchange=ex))
    st_r = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                           exchange=ex)
    for _ in range(3):
        st_s, ms = rnd_s(st_s, batch)
        st_r, mr = rnd_r(st_r, batch)
    np.testing.assert_allclose(np.asarray(st_s["params"]),
                               np.asarray(st_r["params"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(st_s["comm"]["inflight"]["params"]),
        np.asarray(st_r["comm"]["inflight"]["params"]),
        rtol=1e-5, atol=1e-6)
    assert rnd_s.wire_bytes(st_s) == rnd_r.wire_bytes(st_r)


# ---------------------------------------------------------------------------
# tier-1 driver: force 8 host devices in a child process
# ---------------------------------------------------------------------------


def test_suite_under_forced_8_devices():
    """Under the plain 1-device tier-1 run, re-run this module's
    8-device cells with 8 forced host devices in a subprocess (jax locks
    the device count at first init). CI's forced-8-device job runs the
    tests directly and skips this driver (REPRO_SHARDEXEC_CHILD, shared
    with test_shardexec.py)."""
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
         os.path.abspath(__file__),
         "-k", "sharded_overlap"],
        env=env, capture_output=True, text=True, timeout=1800,
        cwd=repo)
    assert r.returncode == 0, (
        f"8-device overlap suite failed:\n{r.stdout[-4000:]}"
        f"\n{r.stderr[-2000:]}")
