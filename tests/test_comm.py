"""Communication subsystem (repro.comm): topologies, codecs, exchanges.

Acceptance-critical invariants (ISSUE 2 / DESIGN.md §8):
  * mixing matrices are doubly stochastic with positive spectral gap, and
    repeated mixing contracts to the G-mean (consensus),
  * the server backend with the fp32 codec is BIT-EXACT with the
    pre-refactor ``average_groups`` on both pytree and packed rounds,
  * int8/topk codecs round-trip within their scale tolerance; the Pallas
    quantize kernels agree with the jnp reference on the same rounding
    bits,
  * error-feedback residuals account exactly: what top-k drops this round
    is re-offered next round (zero drift),
  * every backend preserves the G-mean, wire bytes are exact, and the
    unsupported combinations refuse instead of silently degrading.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, optim
from repro.core import localsgd as lsgd
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.optim import packing

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
# topologies: doubly stochastic, spectral gap, consensus
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["server", "ring", "gossip"])
@pytest.mark.parametrize("m", [1, 2, 3, 4, 7, 16])
def test_mixing_matrix_doubly_stochastic(name, m):
    w = comm.mixing_matrix(name, m, seed=3)
    assert w.shape == (m, m)
    assert comm.is_doubly_stochastic(w)


@pytest.mark.parametrize("name", ["server", "ring", "gossip"])
@pytest.mark.parametrize("m", [3, 5, 8])
def test_mixing_converges_to_consensus(name, m):
    """spectral gap > 0 => W^k x -> mean(x) at rate (1 - gap)^k."""
    w = comm.mixing_matrix(name, m, seed=1)
    gap = comm.spectral_gap(w)
    assert gap > 0.0, (name, m, gap)
    rng = np.random.RandomState(0)
    x = rng.randn(m, 5)
    y = x.copy()
    k = 80
    for _ in range(k):
        y = w @ y
    err = np.abs(y - x.mean(axis=0)).max()
    assert err <= (1.0 - gap) ** k * np.abs(x).max() * m + 1e-9, \
        (name, m, err)


def test_gossip_deterministic_per_seed():
    a = comm.gossip_matrix(8, seed=5)
    b = comm.gossip_matrix(8, seed=5)
    c = comm.gossip_matrix(8, seed=6)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_server_matrix_one_step_consensus():
    w = comm.server_matrix(5)
    x = np.arange(15.0).reshape(5, 3)
    np.testing.assert_allclose(w @ x, np.broadcast_to(x.mean(0), (5, 3)))


# ---------------------------------------------------------------------------
# codecs: round-trips, error feedback, wire bytes
# ---------------------------------------------------------------------------


def test_cast_codec_roundtrip(key):
    x = jax.random.normal(key, (G, 100))
    for name, tol in (("fp16", 1e-3), ("bf16", 1e-2)):
        c = comm.get_codec(name)
        out, state = c.compress(x, {})
        assert state == {}
        np.testing.assert_allclose(out, x, rtol=tol, atol=tol)
        assert c.wire_bytes(100) == 200


def test_int8_roundtrip_within_chunk_scale(key):
    """Stochastic rounding moves each element by at most one quantization
    step (the chunk's scale); padding chunks never leak."""
    chunk = 64
    c = comm.get_codec("int8", chunk=chunk, impl="jnp")
    x = jax.random.normal(key, (G, 150)) * 3.0      # 150: ragged chunks
    out, state = c.compress(x, c.init(x))
    assert int(state["count"]) == 1
    rows = packing.chunk_rows(x, chunk)
    scales = jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 127.0
    err = jnp.abs(packing.chunk_rows(out, chunk) - rows)
    assert bool(jnp.all(err <= scales + 1e-7))
    # payload: 1 byte/elem + one fp32 scale per chunk
    assert c.wire_bytes(150) == 150 + 4 * 3


def test_int8_deterministic_and_unbiased(key):
    c = comm.get_codec("int8", impl="jnp")
    x = jax.random.normal(key, (2, 4096))
    out1, _ = c.compress(x, c.init(x))
    out2, _ = c.compress(x, c.init(x))
    np.testing.assert_array_equal(out1, out2)     # same counter, same bits
    # different counter -> different bits, but zero-mean error
    out3, _ = c.compress(x, {"count": jnp.asarray(7, jnp.int32)})
    assert not np.array_equal(out1, out3)
    assert abs(float(jnp.mean(out1 - x))) < 1e-3


def test_int8_pallas_matches_jnp(key):
    """Both impls consume the same rounding bits -> identical output."""
    cj = comm.get_codec("int8", impl="jnp")
    cp = comm.get_codec("int8", impl="pallas")
    x = jax.random.normal(key, (G, 300))
    oj, _ = cj.compress(x, cj.init(x))
    op, _ = cp.compress(x, cp.init(x))
    np.testing.assert_allclose(op, oj, atol=1e-7)


@pytest.mark.parametrize("rows,chunk", [(1, 64), (6, 256), (13, 128)])
def test_quantize_kernels_vs_oracle(rows, chunk, key):
    """kernels/quantize.py vs the jnp math on the same noise."""
    ks = jax.random.split(key, 2)
    x = jax.random.normal(ks[0], (rows, chunk)) * 2.0
    u = jax.random.uniform(ks[1], (rows, chunk))
    q, scales = quantize_int8(x, u, interpret=True)
    assert q.dtype == jnp.int8 and scales.shape == (rows, 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    want_s = jnp.where(amax > 0, amax / 127.0, 1.0)
    np.testing.assert_allclose(scales, want_s, rtol=1e-6)
    want_q = jnp.clip(jnp.floor(x / want_s + u), -127, 127)
    np.testing.assert_array_equal(q, want_q.astype(jnp.int8))
    out = dequantize_int8(q, scales, interpret=True)
    np.testing.assert_allclose(out, q.astype(jnp.float32) * scales,
                               rtol=1e-6)


def test_quantize_kernel_zero_chunk():
    """An all-zero chunk must quantize to zeros (scale guard)."""
    x = jnp.zeros((2, 64))
    u = jnp.full((2, 64), 0.5)
    q, s = quantize_int8(x, u, interpret=True)
    np.testing.assert_array_equal(q, jnp.zeros((2, 64), jnp.int8))
    np.testing.assert_array_equal(s, jnp.ones((2, 1)))


def test_topk_error_feedback_zero_drift(key):
    """delta + residual_in == delta_hat + residual_out EXACTLY: what the
    wire drops this round is carried, not lost."""
    c = comm.get_codec("topk", topk_frac=0.25)
    x = jax.random.normal(key, (G, 40))
    state = c.init(x)
    for i in range(4):
        delta = jnp.roll(x, i, axis=-1) * (i + 1)
        e_in = state["residual"]
        out, state = c.compress(delta, state)
        # the per-round accounting identity is EXACT: the residual update
        # is the same subtraction that defines what the wire dropped
        np.testing.assert_array_equal(delta + e_in,
                                      out + state["residual"])
        # at most k entries per row on the wire
        k = max(1, round(0.25 * 40))
        assert int(jnp.max(jnp.sum(out != 0.0, axis=-1))) <= k
    assert c.wire_bytes(40) == 8 * 10


# ---------------------------------------------------------------------------
# exchanges: parity, mean preservation, staleness, wire bytes
# ---------------------------------------------------------------------------


def test_server_fp32_bit_exact_with_average_groups_pytree(key):
    """The acceptance parity: the refactored round (server/fp32 through
    comm.Exchange) is BIT-EXACT with averaging the ungrouped round's
    locals via the pre-refactor average_groups. Eager execution: op-by-op
    identical arithmetic."""
    params, batch = make_problem(key)
    opt = optim.momentum(0.05)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=3)
    # "none" topology = the local steps with NO communication
    rnd_none = lsgd.make_local_round(
        quad_loss, opt, cfg, exchange=comm.get_exchange("none", "fp32", G))
    rnd_server = lsgd.make_local_round(
        quad_loss, opt, cfg,
        exchange=comm.get_exchange("server", "fp32", G))
    st = lsgd.init_state(params, opt, n_groups=G)
    locals_, _ = rnd_none(jax.tree.map(jnp.copy, st), batch)
    got, _ = rnd_server(st, batch)
    want_p = lsgd.average_groups(locals_["params"])
    want_o = lsgd.average_groups(locals_["opt"])
    for a, b in zip(jax.tree.leaves(got["params"]),
                    jax.tree.leaves(want_p)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(got["opt"]), jax.tree.leaves(want_o)):
        np.testing.assert_array_equal(a, b)


def test_server_fp32_bit_exact_with_average_groups_packed(key):
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("momentum", 0.05, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=3)
    rnd_none = lsgd.make_local_round(
        quad_loss, opt, cfg, layout=layout,
        exchange=comm.get_exchange("none", "fp32", G))
    rnd_server = lsgd.make_local_round(quad_loss, opt, cfg, layout=layout)
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout)
    locals_, _ = rnd_none(jax.tree.map(jnp.copy, st), batch)
    got, _ = rnd_server(st, batch)
    np.testing.assert_array_equal(
        got["params"], lsgd.average_groups(locals_["params"]))
    np.testing.assert_array_equal(
        got["opt"]["mu"], lsgd.average_groups(locals_["opt"]["mu"]))
    np.testing.assert_array_equal(got["opt"]["count"],
                                  locals_["opt"]["count"])


@pytest.mark.parametrize("topology", ["ring", "gossip"])
def test_decentralized_exchange_preserves_mean(topology, key):
    """Doubly-stochastic mixing keeps the G-mean invariant: decentralized
    rounds optimize the same average objective as the server."""
    ex = comm.get_exchange(topology, "fp32", G, mix_rounds=2)
    x = jax.random.normal(key, (G, 37))
    mixed, state = ex.params(x, None, {})
    assert state == {}
    np.testing.assert_allclose(jnp.mean(mixed, 0), jnp.mean(x, 0),
                               rtol=1e-5, atol=1e-6)
    # groups do NOT reach exact consensus in one ring hop...
    assert float(jnp.abs(mixed - jnp.mean(x, 0)).max()) > 1e-3
    # ...but many hops contract toward it
    ex_k = dataclasses.replace(ex, mix_rounds=60)
    near, _ = ex_k.params(x, None, {})
    assert float(jnp.abs(near - jnp.mean(x, 0)).max()) < 1e-3


def test_decentral_lossy_recompresses_per_hop(key):
    """Multi-hop ring/gossip applies the codec at EVERY mixing hop (each
    hop's payload is a fresh wire transmission — the byte accounting
    always counted per hop; the noise model now matches): the int8 rng
    counter advances once per hop, and error feedback (top-k residual)
    updates per hop while its exact accounting identity still closes over
    the whole round."""
    k = 3
    ex = comm.get_exchange("ring", "int8", 8, mix_rounds=k)
    x0 = jnp.zeros((8, 512))
    x = jax.random.normal(key, (8, 512)) * 0.1
    state = ex.init(x0)
    _, state = ex.params(x, x0, state)
    # per-stream codec state (DESIGN.md §10): the params stream's rng
    # counter advances once per hop
    assert int(state["codec"]["params"]["count"]) == k
    # top-k per-hop error feedback: after the round, delta-minus-residual
    # equals the sum of everything transmitted (nothing lost, only delayed)
    ex_t = comm.get_exchange("ring", "topk", 8, mix_rounds=2,
                             topk_frac=0.1)
    state_t = ex_t.init(x0)
    out_t, state_t = ex_t.params(x, x0, state_t)
    resid = state_t["codec"]["params"]["residual"]
    assert bool(jnp.all(jnp.isfinite(resid)))
    # mean preservation still holds under per-hop top-k: the mixing is
    # doubly stochastic over the DECODED payloads, so the output mean is
    # the input mean minus exactly what still sits in the residual
    want = jnp.mean(x - resid, axis=0)
    np.testing.assert_allclose(jnp.mean(out_t, 0), want,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("codec", ["int8", "topk"])
def test_per_hop_codec_consensus_contracts(codec, key):
    """The ROADMAP follow-up check: with the codec applied at every hop,
    repeated mixing still CONTRACTS disagreement (the per-hop noise is
    bounded by the per-chunk scale / absorbed by error feedback, so it
    cannot undo the spectral-gap contraction at these magnitudes)."""
    m, k = 8, 4
    ex = comm.get_exchange("ring", codec, m, mix_rounds=k, topk_frac=0.25)
    x0 = jnp.zeros((m, 512))
    x = jax.random.normal(key, (m, 512))
    state = ex.init(x0)
    out, _ = ex.params(x, x0, state)
    dis_in = float(jnp.abs(x - jnp.mean(x, 0)).max())
    dis_out = float(jnp.abs(out - jnp.mean(out, 0)).max())
    # ring(8): |lambda_2| ~ 0.80 -> 4 hops contract to ~0.42; leave head-
    # room for codec noise but require a real contraction
    assert dis_out < 0.7 * dis_in, (codec, dis_in, dis_out)


def test_async_stale_s0_equals_server(key):
    ex0 = comm.get_exchange("async_stale", "fp32", G, staleness=0)
    x = jax.random.normal(key, (G, 11))
    state = ex0.init(x * 0.0)
    out, state = ex0.params(x, None, state)
    want = jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape)
    np.testing.assert_array_equal(out, want)


def test_async_stale_bounded_staleness(key):
    """s=1: each round only half the groups refresh their push; the
    average mixes fresh models with <= 1-round-old ones, deterministically
    (numpy re-simulation agrees)."""
    s = 1
    ex = comm.get_exchange("async_stale", "fp32", G, staleness=s)
    x0 = jax.random.normal(key, (G, 5))
    state = ex.init(x0)
    pushed_ref = np.asarray(x0).copy()
    for rnd_i in range(4):
        x = x0 + (rnd_i + 1) * jnp.arange(G)[:, None]
        out, state = ex.params(x, None, state)
        fresh = (np.arange(G) + rnd_i) % (s + 1) == 0
        pushed_ref[fresh] = np.asarray(x)[fresh]
        np.testing.assert_allclose(
            np.asarray(out),
            np.broadcast_to(pushed_ref.mean(0), (G, 5)), rtol=1e-6)
    assert int(state["round"]) == 4


def test_wire_bytes_accounting():
    n = 1000
    cases = {
        ("server", "fp32"): G * 4 * n,
        ("server", "fp16"): G * 2 * n,
        ("server", "int8"): G * (n + 4 * 4),          # 4 chunks of 256
        ("server", "topk"): G * 8 * 50,               # k = 5% of 1000
        ("none", "fp32"): 0,
    }
    for (topo, codec), want in cases.items():
        ex = comm.get_exchange(topo, codec, G)
        assert ex.wire_bytes_up(n) == want, (topo, codec)
        # server broadcast: every group also PULLS the new average at the
        # same codec width; none has no wire at all
        assert ex.wire_bytes_down(n) == want, (topo, codec)
        assert ex.wire_bytes_per_round(n) == 2 * want, (topo, codec)
    # ring: one payload per directed edge per hop (G=4 ring: 8 edges);
    # peer-to-peer symmetry — every edge payload is one node's uplink and
    # its neighbor's downlink, i.e. the SAME transmission seen from both
    # endpoints: the total counts it once (no double-counting)
    ex = comm.get_exchange("ring", "fp32", G, mix_rounds=3)
    assert ex.wire_bytes_up(n) == 8 * 3 * 4 * n
    assert ex.wire_bytes_down(n) == ex.wire_bytes_up(n)
    assert ex.wire_bytes_per_round(n) == ex.wire_bytes_up(n)
    # async s=1: half the groups push per round (amortized), and the
    # downlink answers each push with the fresh average (pull-on-push)
    ex = comm.get_exchange("async_stale", "fp32", G, staleness=1)
    assert ex.wire_bytes_up(n) == G // 2 * 4 * n
    assert ex.wire_bytes_down(n) == G // 2 * 4 * n
    # moment buffers ride at fp32 width
    ex = comm.get_exchange("server", "int8", G)
    assert ex.wire_bytes_up(n, moment_elems=2 * n) == \
        G * ((n + 16) + 4 * 2 * n)
    assert ex.wire_bytes_per_round(n, moment_elems=2 * n) == \
        2 * G * ((n + 16) + 4 * 2 * n)


def test_round_metrics_report_wire_bytes(key):
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    n = layout.size
    opt = optim.packed("adamw", 0.01, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "int8", G)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    _, m = rnd(st, batch)
    m = rnd.wire_bytes(st)
    # adamw: m and v buffers averaged at fp32; count not exchanged
    assert int(m["wire_bytes"]) == ex.wire_bytes_per_round(n, 2 * n)
    assert int(m["wire_bytes_up"]) == ex.wire_bytes_up(n, 2 * n)
    assert int(m["wire_bytes_down"]) == ex.wire_bytes_down(n, 2 * n)
    assert int(m["wire_bytes"]) == (int(m["wire_bytes_up"])
                                    + int(m["wire_bytes_down"]))
    # pytree path: the moment leaves count, the counter never does
    # (it is not exchanged on either path); server up == down
    opt_t = optim.momentum(0.05)
    rnd_t = jax.jit(lsgd.make_local_round(quad_loss, opt_t, cfg))
    st_t = lsgd.init_state(params, opt_t, n_groups=G)
    rnd_t(st_t, batch)
    mt = rnd_t.wire_bytes(st_t)
    assert int(mt["wire_bytes_up"]) == 4 * G * (n + n)
    assert int(mt["wire_bytes"]) == 2 * 4 * G * (n + n)


def test_pytree_counts_stay_lockstep_under_mixing(key):
    """The int32 step counter is never exchanged (map_moments convention,
    both paths): mixing it through the f32 gossip matmul used to truncate
    and drift per-group counts, corrupting adamw's bias correction."""
    params, batch = make_problem(key)
    opt = optim.adamw(0.01)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("gossip", "fp32", G)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G)
    for _ in range(30):
        st, _ = rnd(st, batch)
    c = np.asarray(st["opt"]["count"])
    assert c.dtype == np.int32
    np.testing.assert_array_equal(c, np.full(G, 60, np.int32))


def test_int8_round_converges(key):
    """Delta-coded quantized communication preserves convergence on the
    feasibility problem (the benchmark checks the full frontier)."""
    params, batch = make_problem(key, r=3, d=8)
    layout = packing.layout_of(params)
    opt = optim.packed("sgd", 0.2, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4)
    ex = comm.get_exchange("server", "int8", G)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    st, m0 = rnd(st, batch)
    for _ in range(60):
        st, m = rnd(st, batch)
    assert float(jnp.mean(m["grad_sq"])) < 1e-3 * float(
        jnp.mean(m0["grad_sq"]))


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------


def test_flat_only_codec_needs_layout(key):
    params, _ = make_problem(key)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    for codec in ("int8", "topk"):
        with pytest.raises(NotImplementedError):
            lsgd.make_local_round(
                quad_loss, optim.sgd(0.1), cfg,
                exchange=comm.get_exchange("server", codec, G))


def test_async_stale_averages_opt_state_with_staleness_buffers(key):
    """The lifted restriction (DESIGN.md §10): async_stale keeps one
    staleness buffer PER STREAM (params under "pushed", each moment under
    "pushed_opt"), so rounds may average opt state. The moments follow
    the same deterministic push schedule as the params."""
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("momentum", 0.05, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)  # avg_opt default
    ex = comm.get_exchange("async_stale", "fp32", G, staleness=1)
    assert ex.supports_opt_state_averaging
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    assert set(st["comm"]) == {"pushed", "pushed_opt", "round"}
    assert st["comm"]["pushed_opt"]["mu"].shape == st["params"].shape
    # numpy re-simulation of the per-stream staleness schedule
    pushed_ref = {"params": np.asarray(st["params"]).copy(),
                  "mu": np.asarray(st["opt"]["mu"]).copy()}
    for rnd_i in range(4):
        pre = {"params": st["params"], "mu": st["opt"]["mu"]}
        st, _ = rnd(st, batch)
        fresh = (np.arange(G) + rnd_i) % 2 == 0
        # re-run the local steps without comm to get this round's locals
        ex_none = comm.get_exchange("none", "fp32", G)
        rnd_none = jax.jit(lsgd.make_local_round(
            quad_loss, opt, cfg, layout=layout, exchange=ex_none))
        loc, _ = rnd_none({"params": pre["params"],
                           "opt": {"count": st["opt"]["count"] - 2,
                                   "mu": pre["mu"]}}, batch)
        for name, val in (("params", loc["params"]),
                          ("mu", loc["opt"]["mu"])):
            pushed_ref[name][fresh] = np.asarray(val)[fresh]
        np.testing.assert_allclose(
            np.asarray(st["params"]),
            np.broadcast_to(pushed_ref["params"].mean(0),
                            st["params"].shape), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(st["opt"]["mu"]),
            np.broadcast_to(pushed_ref["mu"].mean(0),
                            st["opt"]["mu"].shape), rtol=1e-5, atol=1e-6)
        # the NEXT round mixes from the refreshed buffers, so keep the
        # reference in sync with what the round actually pushed
        pushed_ref = {"params": np.asarray(st["comm"]["pushed"]).copy(),
                      "mu": np.asarray(st["comm"]["pushed_opt"]["mu"])
                      .copy()}


def test_stateful_exchange_needs_init_state(key):
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("sgd", 0.1, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "topk", G)
    rnd = lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                exchange=ex)
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout)  # no comm
    with pytest.raises(ValueError):
        rnd(st, batch)


def test_exchange_group_mismatch_raises(key):
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    with pytest.raises(ValueError):
        lsgd.make_local_round(
            quad_loss, optim.sgd(0.1), cfg,
            exchange=comm.get_exchange("server", "fp32", G + 1))


def test_async_stale_refuses_topk():
    """Staleness drops non-pushed rounds by design; error feedback would
    absorb their top-k entries as delivered and silently lose them."""
    with pytest.raises(NotImplementedError):
        comm.get_exchange("async_stale", "topk", G, staleness=1)


def test_none_topology_skips_codec(key):
    """A no-comm baseline must not inject quantization noise (and must
    report zero wire bytes)."""
    ex = comm.get_exchange("none", "int8", G)
    x = jax.random.normal(key, (G, 50))
    x0 = jnp.zeros_like(x)
    # no wire -> no codec state either: nothing to allocate or carry
    assert not ex.stateful and ex.init(x0) == {}
    out, _ = ex.params(x, x0, {})
    np.testing.assert_array_equal(out, x)
    assert ex.wire_bytes_per_round(50) == 0
    # and no layout requirement: the flat-only codec never executes
    params = {"w": jnp.zeros(5)}
    lsgd.make_local_round(quad_loss, optim.sgd(0.1),
                          lsgd.LocalSGDConfig(n_groups=G, inner_steps=1),
                          exchange=ex)


def test_builder_meta_wire_bytes_counts_moments():
    """Dry-run meta must agree with the round's own metrics["wire_bytes"]
    (adamw: 2 moment buffers ride at fp32)."""
    from repro.configs.base import InputShape, get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    mesh = make_local_mesh(1, 1)
    shape = InputShape(name="tiny", kind="train", global_batch=4,
                       seq_len=8)
    built = build_train_step(cfg, shape, mesh, t_inner=2,
                             opt_name="adamw", packed=True)
    n = built.meta["n_flat"]
    ex = comm.get_exchange("server", "fp32", built.meta["groups"])
    assert built.meta["wire_bytes_per_round"] == \
        ex.wire_bytes_per_round(n, 2 * n)


def test_unknown_names_raise():
    with pytest.raises(ValueError):
        comm.get_exchange("mesh?", "fp32", G)
    with pytest.raises(ValueError):
        comm.get_codec("fp8")
    with pytest.raises(ValueError):
        comm.mixing_matrix("star", 4)


# ---------------------------------------------------------------------------
# multi-stream payloads: per-stream codec policy (DESIGN.md §10)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("opt_name", ["momentum", "adamw"])
@pytest.mark.parametrize("topology", ["server", "ring"])
def test_fp32_moment_codec_bit_exact_vs_map_moments(opt_name, topology,
                                                    key):
    """THE §10 parity gate (replicated): with moment_codec=fp32 the
    stream exchange must be BIT-exact with the old map_moments path —
    run the locals with no comm, then mix params and moments by hand
    with exch.params + optim.map_moments(exch.mix) and compare."""
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed(opt_name, 0.03, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=3)
    ex = comm.get_exchange(topology, "fp32", G, mix_rounds=2)
    assert ex.mcodec.identity
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    rnd_none = jax.jit(lsgd.make_local_round(
        quad_loss, opt, cfg, layout=layout,
        exchange=comm.get_exchange("none", "fp32", G)))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout)
    locals_, _ = rnd_none(jax.tree.map(jnp.copy, st), batch)
    got, _ = rnd(st, batch)
    want_p, _ = ex.params(locals_["params"], None, {})
    want_o = optim.map_moments(ex.mix, locals_["opt"])
    np.testing.assert_array_equal(np.asarray(got["params"]),
                                  np.asarray(want_p))
    for k in locals_["opt"]:
        np.testing.assert_array_equal(np.asarray(got["opt"][k]),
                                      np.asarray(want_o[k]), err_msg=k)


def test_moment_codec_wire_accounting_per_stream():
    """Per-stream accounting (§10): each moment stream through the
    moment codec (the fp32 surcharge is gone), old totals == sums."""
    n = 1024
    ms = {"m": n, "v": n}
    ex = comm.get_exchange("server", "int8", G, moment_codec="int8")
    pb = n + 4 * 4                      # int8 payload: 4 chunks of 256
    by = ex.wire_bytes_by_stream(n, ms)
    assert by == {"params": 2 * G * pb, "m": 2 * G * pb, "v": 2 * G * pb}
    assert ex.wire_bytes_per_round(n, moment_sizes=ms) \
        == sum(by.values())
    assert ex.wire_bytes_up(n, moment_sizes=ms) == 3 * G * pb
    assert ex.wire_bytes_down(n, moment_sizes=ms) == 3 * G * pb
    # bf16 moments: 2 bytes/elem while params stay int8
    ex2 = comm.get_exchange("server", "int8", G, moment_codec="bf16")
    by2 = ex2.wire_bytes_by_stream(n, ms)
    assert by2["params"] == 2 * G * pb
    assert by2["m"] == by2["v"] == 2 * G * 2 * n
    # legacy single-blob moment_elems stays the old fp32 number
    ex3 = comm.get_exchange("server", "int8", G)
    assert ex3.wire_bytes_up(n, moment_elems=2 * n) == \
        G * (pb + 4 * 2 * n)
    # p2p totals count each edge payload once, per stream too
    ex4 = comm.get_exchange("ring", "fp32", G, moment_codec="bf16")
    by4 = ex4.wire_bytes_by_stream(n, ms)
    assert by4["params"] == 8 * 4 * n           # G=4 ring: 8 edges
    assert by4["m"] == 8 * 2 * n
    assert ex4.wire_bytes_per_round(n, moment_sizes=ms) == \
        ex4.wire_bytes_up(n, moment_sizes=ms)


def test_moment_codec_round_metrics_per_stream(key):
    """Round metrics report wire_bytes/<stream> with the totals as exact
    sums (adamw: params + m + v through their own codecs)."""
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    n = layout.size
    opt = optim.packed("adamw", 0.01, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "int8", G, moment_codec="int8")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    _, m = rnd(st, batch)
    m = rnd.wire_bytes(st)
    by = ex.wire_bytes_by_stream(n, {"m": n, "v": n})
    for k, v in by.items():
        assert int(m[f"wire_bytes/{k}"]) == v, k
    assert int(m["wire_bytes"]) == sum(by.values())
    assert int(m["wire_bytes"]) == (int(m["wire_bytes_up"])
                                    + int(m["wire_bytes_down"]))
    # vs the old accounting: moments no longer ride at 4 bytes/elem
    old_total = comm.get_exchange("server", "int8", G).wire_bytes_per_round(
        n, moment_elems=2 * n)
    assert int(m["wire_bytes"]) < old_total


def test_moment_codec_per_stream_state(key):
    """Each stream keeps its OWN codec state: adamw + int8 everywhere
    gives three rng counters (params/m/v), all advancing per round."""
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("adamw", 0.01, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "int8", G, moment_codec="int8")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    assert set(st["comm"]["codec"]) == {"params", "m", "v"}
    for _ in range(3):
        st, _ = rnd(st, batch)
    for k in ("params", "m", "v"):
        assert int(st["comm"]["codec"][k]["count"]) == 3, k


@pytest.mark.parametrize("moment_codec", ["bf16", "int8"])
def test_lossy_moment_codec_converges_and_tracks_fp32(moment_codec, key):
    """Lossy moment codecs on the feasibility problem: delta coding makes
    the moment quantization error vanish with convergence — the run
    converges AND tracks the fp32-moments run closely."""
    params, batch = make_problem(key, r=3, d=8)
    layout = packing.layout_of(params)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4)
    outs = {}
    for mc in ("fp32", moment_codec):
        opt = optim.packed("momentum", 0.05, impl="jnp")
        ex = comm.get_exchange("server", "int8", G, moment_codec=mc)
        rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                            layout=layout, exchange=ex))
        st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                             exchange=ex)
        st, m0 = rnd(st, batch)
        for _ in range(80):
            st, m = rnd(st, batch)
        assert float(jnp.mean(m["grad_sq"])) < 1e-4 * float(
            jnp.mean(m0["grad_sq"])), mc
        outs[mc] = np.asarray(st["params"][0])
    scale = np.abs(outs["fp32"]).max() + 1e-12
    rel = np.abs(outs[moment_codec] - outs["fp32"]).max() / scale
    assert rel <= 1e-2, (moment_codec, rel)


def test_nonneg_moment_stream_clamped(key):
    """adamw's v must never go negative through a lossy moment codec
    (sqrt(v) would NaN): the round projects it back onto [0, inf)."""
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("adamw", 0.05, impl="jnp")
    assert opt.moment_nonneg == ("v",)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "int8", G, moment_codec="int8")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    for _ in range(5):
        st, _ = rnd(st, batch)
        assert bool(jnp.all(st["opt"]["v"] >= 0.0))
        assert bool(jnp.all(jnp.isfinite(st["params"])))


def test_topk_moment_codec_refused():
    """topk moments stay excluded (§10): error feedback would re-offer
    rounds-stale moment mass."""
    with pytest.raises(NotImplementedError):
        comm.get_exchange("server", "fp32", G, moment_codec="topk")
    with pytest.raises(NotImplementedError):
        comm.get_exchange("ring", "int8", G, moment_codec="topk")


def test_flat_only_moment_codec_needs_layout(key):
    """int8 moments need the packed flat buffers; cast moment codecs
    (bf16) run on the pytree path too."""
    params, batch = make_problem(key)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    with pytest.raises(NotImplementedError):
        lsgd.make_local_round(
            quad_loss, optim.momentum(0.05), cfg,
            exchange=comm.get_exchange("server", "fp32", G,
                                       moment_codec="int8"))
    # average_opt_state=False: the moment codec never runs -> no refusal
    cfg_off = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2,
                                  average_opt_state=False)
    lsgd.make_local_round(
        quad_loss, optim.momentum(0.05), cfg_off,
        exchange=comm.get_exchange("server", "fp32", G,
                                   moment_codec="int8"))
    # bf16 moments on the pytree path: runs, and the moments move
    ex = comm.get_exchange("server", "fp32", G, moment_codec="bf16")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, optim.momentum(0.05),
                                        cfg, exchange=ex))
    st = lsgd.init_state(params, optim.momentum(0.05), n_groups=G)
    out, m = rnd(st, batch)
    m = {**m, **rnd.wire_bytes(st)}
    assert bool(jnp.all(jnp.isfinite(jax.tree.leaves(out["opt"]["mu"])[0])))
    # bf16 moments halve the moment wire term in the metrics
    n = sum(l.size for l in jax.tree.leaves(params))
    assert int(m["wire_bytes/mu"]) == 2 * G * 2 * n


def test_adaptive_t_from_exchange_prices_moment_streams():
    """AdaptiveT.from_exchange: r reflects the moment codec (§10) — int8
    moments make comm cheaper, so r rises with the full stream payload
    priced, not the fp32-moments assumption."""
    from repro.core.controller import AdaptiveT

    n = 1_000_000
    ms = {"m": n, "v": n}
    step = 2e-6
    ctl_fp32 = AdaptiveT.from_exchange(
        step, comm.get_exchange("server", "int8", 2), n, ms)
    ctl_int8 = AdaptiveT.from_exchange(
        step, comm.get_exchange("server", "int8", 2, moment_codec="int8"),
        n, ms)
    assert ctl_int8.r > 2.5 * ctl_fp32.r
    ex = comm.get_exchange("server", "int8", 2, moment_codec="int8")
    want = ex.wire_bytes_per_round(n, moment_sizes=ms)
    assert abs(ctl_int8.r - step / (want / 50e9)) < 1e-12


@pytest.mark.parametrize("s_stale", [1, 2])
def test_async_avg_opt_state_converges(s_stale, key):
    """The §10 acceptance run: async_stale with average_opt_state=True
    (per-stream staleness buffers) converges on the convex feasibility
    problem under bounded staleness s — moments riding the stale
    averaging must not destabilize it."""
    params, batch = make_problem(key, r=3, d=8)
    layout = packing.layout_of(params)
    opt = optim.packed("momentum", 0.05, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4)  # avg_opt on
    ex = comm.get_exchange("async_stale", "fp32", G, staleness=s_stale)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    st, m0 = rnd(st, batch)
    for _ in range(120):
        st, m = rnd(st, batch)
    assert float(jnp.mean(m["grad_sq"])) < 1e-6 * float(
        jnp.mean(m0["grad_sq"])), s_stale
    # the staleness wire amortization prices the moment stream too:
    # amortized senders G/(s+1) times the fp32 moment payload, up+down
    n = layout.size
    want = 2 * int(round(G / (s_stale + 1) * 4 * n))
    assert rnd.wire_bytes(st)["wire_bytes/mu"] == want
    assert ex.wire_bytes_by_stream(n, {"mu": n})["mu"] == want
