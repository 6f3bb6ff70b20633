"""Bandwidth-optimal exchange engine (ISSUE 5 / DESIGN.md §11).

Acceptance-critical invariants:
  * the fused codec-mix epilogue (kernels/exchange_epilogue.py) is
    BIT-identical to the staged reference path for int8/fp16/bf16 x
    server/ring/gossip x jnp/pallas, and Exchange.streams routes the
    flat-buffer hot path through it by default,
  * the ppermute neighbor hop is bit-exact vs the all_gather hop (same
    assembled rows, same W-row contraction) while shipping only
    O(deg·shard) wire (neighbor_offsets / edge-true accounting),
  * sharded top-k (distributed threshold selection + shard-local EF
    residual) selects at most k entries, never the zero pad, keeps the
    EF identity exactly, and convergence-matches the replicated exact
    selection,
  * the downlink codec compresses the broadcast reply independently of
    the uplink with its own state + wire accounting; the default stays
    bit-exact with the pre-§11 rounds,
  * property-style pad invariants: the zero-pad tail of a ShardedLayout
    is a fixed point of the ppermute hop, the fused epilogue, and the
    sharded top-k selection,
  * the billion-param packed guard refuses int32-overflowing layouts
    with the limit stated (launch/dryrun satellite).

8-device tests ride the same forced-host child-process pattern as
tests/test_shardexec.py (REPRO_SHARDEXEC_CHILD gates the in-suite
driver so CI's dedicated 8-device job doesn't pay twice).
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, optim
from repro.comm import topology as topo
from repro.core import localsgd as lsgd
from repro.kernels import exchange_epilogue as ee
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


# ---------------------------------------------------------------------------
# topology: offset decomposition (no devices needed)
# ---------------------------------------------------------------------------


def test_neighbor_offsets_ring_is_edge_true():
    """A ring's off-diagonal support is exactly the offsets {1, m-1}, so
    the ppermute hop ships n_edge_sends payloads — edge-true wire."""
    for m in (4, 8, 16):
        w = topo.ring_matrix(m)
        offs = topo.neighbor_offsets(w)
        assert offs == (1, m - 1), (m, offs)
        assert topo.n_edge_sends(w) == 2 * m == len(offs) * m
        ow = topo.offset_weights(w, offs)
        assert ow.shape == (2, m)
        np.testing.assert_allclose(ow, 1.0 / 3.0)


def test_neighbor_offsets_gossip_covers_support():
    """Every nonzero W[i,j] is reachable at one of the offsets, and the
    offset weights reproduce W's off-diagonal row entries."""
    w = topo.gossip_matrix(8, seed=3)
    offs = topo.neighbor_offsets(w)
    ow = topo.offset_weights(w, offs)
    got = np.zeros_like(w)
    g = np.arange(8)
    for di, d in enumerate(offs):
        got[g, (g + d) % 8] = ow[di]
    off = w.copy()
    np.fill_diagonal(off, 0.0)
    np.testing.assert_allclose(got, off, atol=1e-12)
    # the union-of-offsets ship count upper-bounds the edge-true count
    assert topo.n_edge_sends(w) <= len(offs) * 8


# ---------------------------------------------------------------------------
# fused codec-mix epilogue: bit-identity + pad fixed point (replicated)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topology", ["server", "ring", "gossip"])
@pytest.mark.parametrize("codec", ["int8", "bf16", "fp16"])
def test_fused_stream_bit_identical_to_staged(topology, codec, key):
    """THE §11 fused-epilogue gate: Exchange.streams with the fused
    codec-mix epilogue (default) is BIT-identical to the staged
    reference path (fused=False), including the codec state counter."""
    mr = 1 if topology == "server" else 3
    ex = comm.get_exchange(topology, codec, G, mix_rounds=mr, impl="jnp")
    staged = dataclasses.replace(ex, fused=False)
    x0 = jax.random.normal(key, (G, 700))
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1), x0.shape) * 0.1
    st = ex.init(x0)
    out_f, st_f = jax.jit(ex.params)(x, x0, st)
    out_s, st_s = jax.jit(staged.params)(x, x0, st)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_s))
    if codec == "int8":
        assert int(st_f["codec"]["params"]["count"]) \
            == int(st_s["codec"]["params"]["count"]) == mr


def test_fused_epilogue_pallas_bit_identical_to_jnp(key):
    """The Pallas kernel (interpret mode on CPU) and the jnp reference
    consume the same inputs and agree exactly — including the fused qdq
    kernel the int8 codec's pallas impl now routes through."""
    x0 = jax.random.normal(key, (G, 700))
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1), x0.shape) * 0.1
    for topology, mr in (("server", 1), ("ring", 2)):
        ex_p = comm.get_exchange(topology, "int8", G, mix_rounds=mr,
                                 impl="pallas")
        ex_j = comm.get_exchange(topology, "int8", G, mix_rounds=mr,
                                 impl="jnp")
        st = ex_p.init(x0)
        op, _ = jax.jit(ex_p.params)(x, x0, st)
        oj, _ = jax.jit(ex_j.params)(x, x0, st)
        np.testing.assert_array_equal(np.asarray(op), np.asarray(oj))
    # qdq_int8 == quantize_int8 + dequantize_int8, bit for bit
    from repro.kernels.quantize import dequantize_int8, quantize_int8
    rows = jax.random.normal(key, (6, 256))
    u = jax.random.uniform(jax.random.fold_in(key, 2), rows.shape)
    fused = ee.qdq_int8(rows, u, interpret=True)
    q, s = quantize_int8(rows, u, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(dequantize_int8(
                                      q, s, interpret=True)))


@pytest.mark.parametrize("kind", ["int8", "bf16", "thresh"])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_fused_epilogue_pad_is_fixed_point(kind, impl, key):
    """Property (ISSUE 5 satellite): a zero tail (the ShardedLayout pad)
    stays exactly zero through the fused epilogue — zero chunks quantize
    to zero, casts keep zero, thresh never selects |c| = 0 — and the
    thresh residual stays zero in the pad."""
    n_real, pad = 300, 212
    n = n_real + pad
    mask = (jnp.arange(n) < n_real).astype(jnp.float32)
    x0 = jax.random.normal(key, (G, n)) * mask
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1),
                               (G, n)) * 0.1 * mask
    kw = dict(kind=kind, impl=impl, interpret=True)
    if kind == "int8":
        chunk = 256
        rows = (G * ((n + chunk - 1) // chunk), chunk)
        c = comm.get_codec("int8", impl="jnp")
        kw.update(chunk=chunk, u=c.noise(jnp.zeros((), jnp.int32), rows)
                  [None])
    if kind == "thresh":
        kw.update(residual=jnp.zeros_like(x),
                  tau=jnp.full((G, 1), 0.05, jnp.float32))
    mixed, res = ee.codec_mix(x, x0, **kw)
    np.testing.assert_array_equal(np.asarray(mixed[:, n_real:]), 0.0)
    if res is not None:
        np.testing.assert_array_equal(np.asarray(res[:, n_real:]), 0.0)


def test_fused_server_topk_stream_matches_staged(key):
    """Server top-k routes through the fused thresh epilogue by default
    (DESIGN.md §11): multi-round Exchange.streams — residual threading
    included — matches the staged exact-selection path bit for bit on
    tie-free data, for both kernel impls."""
    for impl in ("jnp", "pallas"):
        ex = comm.get_exchange("server", "topk", G, topk_frac=0.1,
                               impl=impl)
        assert ex.codec.impl == impl
        staged = dataclasses.replace(ex, fused=False)
        x0 = jax.random.normal(key, (G, 300))
        st_f, st_s = ex.init(x0), ex.init(x0)
        for i in range(3):
            x = x0 + jax.random.normal(jax.random.fold_in(key, i),
                                       x0.shape) * 0.1
            out_f, st_f = jax.jit(ex.params)(x, x0, st_f)
            out_s, st_s = jax.jit(staged.params)(x, x0, st_s)
            np.testing.assert_array_equal(np.asarray(out_f),
                                          np.asarray(out_s))
            np.testing.assert_array_equal(
                np.asarray(st_f["codec"]["params"]["residual"]),
                np.asarray(st_s["codec"]["params"]["residual"]))
            x0 = out_f
    # ring top-k keeps the staged per-hop path (no thresh fusion there)
    ex_r = comm.get_exchange("ring", "topk", G, mix_rounds=2)
    assert not ex_r._fusable(ex_r.codec, jnp.zeros((G, 8)))


def test_fused_thresh_matches_exact_topk_without_ties(key):
    """With tau = the exact k-th |c| (no ties in generic data), the
    fused thresh epilogue reproduces the staged exact-top-k server
    exchange bit for bit."""
    frac = 0.1
    n = 512
    ex = dataclasses.replace(
        comm.get_exchange("server", "topk", G, topk_frac=frac),
        fused=False)   # the STAGED exact-selection reference
    x0 = jax.random.normal(key, (G, n))
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1), x0.shape) * 0.1
    st = ex.init(x0)
    out_staged, st_staged = jax.jit(ex.params)(x, x0, st)
    k = max(1, round(frac * n))
    c = x - x0   # residual starts zero
    tau = jax.lax.top_k(jnp.abs(c), k)[0][:, -1:]
    mixed, res = ee.codec_mix(x, x0, kind="thresh", residual=jnp.zeros_like(c),
                              tau=tau, impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(mixed), np.asarray(out_staged))
    np.testing.assert_array_equal(
        np.asarray(res), np.asarray(st_staged["codec"]["params"]["residual"]))


# ---------------------------------------------------------------------------
# downlink codec (replicated path)
# ---------------------------------------------------------------------------


def test_downlink_default_and_fp32_bit_exact(key):
    """No downlink codec (default) and an explicit fp32 downlink are both
    bit-exact with the pre-§11 exchange — the knob only changes the
    accounting width in the fp32 case."""
    x0 = jax.random.normal(key, (G, 300))
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1), x0.shape) * 0.1
    base = comm.get_exchange("server", "int8", G, impl="jnp")
    dl32 = comm.get_exchange("server", "int8", G, impl="jnp",
                             downlink_codec="fp32")
    st = base.init(x0)
    ob, _ = jax.jit(base.params)(x, x0, st)
    o32, _ = jax.jit(dl32.params)(x, x0, dl32.init(x0))
    np.testing.assert_array_equal(np.asarray(ob), np.asarray(o32))
    # accounting: default prices the downlink at the uplink width;
    # fp32 downlink prices it at 4 bytes/elem
    n = 300
    assert base.wire_bytes_down(n) == G * base.codec.wire_bytes(n)
    assert dl32.wire_bytes_down(n) == G * 4 * n
    assert base.wire_bytes_up(n) == dl32.wire_bytes_up(n)


def test_downlink_codec_noise_and_state(key):
    """A lossy downlink injects bounded broadcast noise, keeps its own
    per-stream reference + codec state under comm["down"], and its
    delta coding makes the noise vanish as the mean converges."""
    x0 = jax.random.normal(key, (G, 300))
    ex = comm.get_exchange("server", "fp32", G, downlink_codec="int8",
                           impl="jnp")
    assert ex.stateful and ex.name == "server/fp32+d:int8"
    st = ex.init(x0)
    assert set(st["down"]) == {"params"}
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1), x0.shape) * 0.1
    out, st = jax.jit(ex.params)(x, x0, st)
    want = jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape)
    err0 = float(jnp.max(jnp.abs(out - want)))
    assert 0 < err0 < 0.05
    # every group receives the SAME decoded broadcast
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    assert int(st["down"]["params"]["state"]["count"]) == 1
    # re-broadcasting an unchanged mean: the delta vs the stored ref
    # shrinks, so the decode error shrinks with it
    out2, st = jax.jit(ex.params)(x, x0, st)
    err1 = float(jnp.max(jnp.abs(out2 - want)))
    assert err1 <= err0 + 1e-7


def test_downlink_round_level_accounting_and_clamp(key):
    """A packed adamw round with an int8 downlink: wire_bytes_down in
    the metrics matches the static accounting at the DOWNLINK width, the
    down state threads through the train state, and the non-negative
    moment projection also covers downlink-noised v."""
    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("adamw", 0.02, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "fp32", G, downlink_codec="int8")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    assert set(st["comm"]["down"]) == {"params", "m", "v"}
    for _ in range(3):
        st, m = rnd(st, batch)
    m = rnd.wire_bytes(st)
    n = layout.padded
    sizes = {k: n for k in opt.moment_keys}
    assert int(m["wire_bytes_down"]) == ex.wire_bytes_down(
        n, moment_sizes=sizes)
    assert int(m["wire_bytes_up"]) == ex.wire_bytes_up(
        n, moment_sizes=sizes)
    assert int(m["wire_bytes"]) == ex.wire_bytes_per_round(
        n, moment_sizes=sizes)
    # int8 downlink (1B + scales) is cheaper than the fp32 uplink here
    assert m["wire_bytes_down"] < m["wire_bytes_up"]
    # v came through a lossy broadcast: the clamp kept it non-negative
    assert float(jnp.min(st["opt"]["v"])) >= 0.0


def test_downlink_refusals():
    for topo_ in ("ring", "gossip", "none"):
        with pytest.raises(NotImplementedError):
            comm.get_exchange(topo_, "fp32", G, downlink_codec="int8")
    with pytest.raises(NotImplementedError):
        comm.get_exchange("server", "fp32", G, downlink_codec="topk")
    # flat-only downlink needs the packed wire format
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    with pytest.raises(NotImplementedError):
        lsgd.make_local_round(
            quad_loss, optim.sgd(0.1), cfg,
            exchange=comm.get_exchange("server", "fp32", G,
                                       downlink_codec="int8"))


def test_downlink_checkpoint_roundtrip(key, tmp_path):
    """The nested down state (per-stream ref + codec counter) survives a
    checkpoint round trip bit-exactly (same contract as §10 states)."""
    from repro.checkpoint import io as ckpt_io

    params, batch = make_problem(key)
    layout = packing.layout_of(params)
    opt = optim.packed("momentum", 0.05, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "int8", G, downlink_codec="bf16")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    st, _ = rnd(st, batch)
    path = str(tmp_path / "ck")
    ckpt_io.save(path, st, metadata={})
    back = ckpt_io.load(path, st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st2, _ = rnd(back, batch)
    stc, _ = rnd(st, batch)
    for a, b in zip(jax.tree.leaves(st2), jax.tree.leaves(stc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# billion-param packed guard (launch/dryrun satellite)
# ---------------------------------------------------------------------------


def test_packed_index_space_guard():
    """The billion-param packed dryrun used to die mid-lower with a bare
    int32 OverflowError (PR 3 note); now the layout math refuses up
    front with the limit stated."""
    big = packing.Layout(treedef=None, shapes=((10**9,),),
                         dtypes=(jnp.float32,), offsets=(0,),
                         sizes=(10**9,), size=10**9)
    packing.check_packed_index_space(big, 2)          # 2e9 < 2^31-1: ok
    with pytest.raises(NotImplementedError, match="2\\*\\*31-1"):
        packing.check_packed_index_space(big, 3)      # 3e9: refused
    huge = dataclasses.replace(big, shapes=((3 * 10**9,),),
                               sizes=(3 * 10**9,), size=3 * 10**9)
    with pytest.raises(NotImplementedError):
        packing.check_packed_index_space(huge)
    # the packed round builder hits the guard before any tracing
    cfg = lsgd.LocalSGDConfig(n_groups=3, inner_steps=1)
    opt = optim.packed("sgd", 0.1, impl="jnp")
    with pytest.raises(NotImplementedError, match="int32 index space"):
        lsgd.make_local_round(quad_loss, opt, cfg, layout=big)
    with pytest.raises(NotImplementedError):
        lsgd.make_sync_step(quad_loss, opt, layout=huge)


# ---------------------------------------------------------------------------
# 8-device mesh: ppermute parity, sharded top-k
# ---------------------------------------------------------------------------


@needs8
@pytest.mark.parametrize("topology", ["ring", "gossip"])
def test_ppermute_hop_bit_exact_vs_allgather(topology, key):
    """THE §11 hop gate: the ppermute neighbor hop assembles the same
    (G, shard) rows the all_gather produced (absent neighbors zero) and
    contracts with the same W row — codec-free mixing AND the full int8
    multi-stream exchange are bit-exact between the two hop impls, and
    both match the replicated path."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    assert sexec.hop_impl == "ppermute"
    sexec_ag = dataclasses.replace(sexec, hop_impl="allgather")
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    x0 = packing.pack(lsgd.replicate(params, G), layout)
    mask = (jnp.arange(layout.padded) < layout.size).astype(jnp.float32)
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1),
                               x0.shape) * 0.1 * mask
    ex = comm.get_exchange(topology, "fp32", G, mix_rounds=3)
    mp = jax.jit(sexec.mix(ex))(x)
    ma = jax.jit(sexec_ag.mix(ex))(x)
    np.testing.assert_array_equal(np.asarray(mp), np.asarray(ma))
    # and <= 1e-5 vs the replicated mixing (reduction-order only)
    np.testing.assert_allclose(np.asarray(mp), np.asarray(ex.mix(x)),
                               rtol=1e-5, atol=1e-6)
    ex8 = comm.get_exchange(topology, "int8", G, mix_rounds=2, impl="jnp",
                            moment_codec="int8")
    moments = {"mu": x * 0.5}
    st = ex8.init(x0, moments=moments)
    fp = jax.jit(sexec.exchange_streams(ex8, layout))
    fa = jax.jit(sexec_ag.exchange_streams(ex8, layout))
    xs = {"params": x, "mu": x * 0.5}
    xs0 = {"params": x0, "mu": x0 * 0.5}
    op, sp = fp(xs, xs0, st)
    oa, sa = fa(xs, xs0, st)
    for k in xs:
        np.testing.assert_array_equal(np.asarray(op[k]), np.asarray(oa[k]))
    orr, _ = jax.jit(ex8.streams)(xs, xs0, st)
    for k in xs:
        np.testing.assert_allclose(np.asarray(op[k]), np.asarray(orr[k]),
                                   rtol=1e-5, atol=1e-6)


@needs8
def test_ppermute_pad_is_fixed_point(key):
    """Property (ISSUE 5 satellite): the zero-pad tail stays exactly
    zero through ppermute hops (a convex combination of zeros)."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    x0 = packing.pack(lsgd.replicate(params, G), layout)
    mask = (jnp.arange(layout.padded) < layout.size).astype(jnp.float32)
    x = x0 + jax.random.normal(key, x0.shape) * mask
    assert layout.padded > layout.size   # there IS a pad to check
    for topology in ("ring", "gossip"):
        ex = comm.get_exchange(topology, "fp32", G, mix_rounds=4)
        out = np.asarray(jax.jit(sexec.mix(ex))(x))
        np.testing.assert_array_equal(out[:, layout.size:], 0.0)


@needs8
def test_sharded_topk_selection_properties(key):
    """Sharded top-k (DESIGN.md §11): at most k entries selected per
    group, the zero pad is NEVER selected, the shard-local residual
    keeps the EF identity exactly and stays zero in the pad."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    x0 = packing.pack(lsgd.replicate(params, G), layout)
    mask = (jnp.arange(layout.padded) < layout.size).astype(jnp.float32)
    x = x0 + jax.random.normal(jax.random.fold_in(key, 1),
                               x0.shape) * 0.1 * mask
    frac = 0.02
    ex = comm.get_exchange("server", "topk", G, topk_frac=frac)
    k = max(1, round(frac * layout.padded))
    assert k < layout.size   # a real selection, not select-everything
    out, st = jax.jit(sexec.exchange(ex, layout))(x, x0, ex.init(x0))
    res = np.asarray(st["codec"]["params"]["residual"])
    c = np.asarray(x - x0)
    d_hat = c - res          # EF identity: c == d_hat + residual exactly
    nsel = (d_hat != 0).sum(axis=1)
    assert (nsel <= k).all(), (nsel, k)
    assert (nsel >= 1).all()
    np.testing.assert_array_equal(d_hat[:, layout.size:], 0.0)
    np.testing.assert_array_equal(res[:, layout.size:], 0.0)
    # every shipped entry beats every kept entry (threshold selection)
    for g in range(G):
        shipped = np.abs(d_hat[g][d_hat[g] != 0])
        kept = np.abs(res[g][(d_hat[g] == 0) & (c[g] != 0)])
        if shipped.size and kept.size:
            assert shipped.min() >= kept.max() - 1e-12


@needs8
def test_sharded_topk_matches_replicated_convergence(key):
    """The §11 convergence gate at test scale: multi-round packed topk
    rounds — sharded (distributed threshold) vs replicated (exact
    selection) — converge to the same feasibility point; the selection
    deviation only re-orders WHEN near-threshold mass ships."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, batch = make_problem(key, r=24, d=32)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    ex = comm.get_exchange("server", "topk", G, topk_frac=0.05)
    opt_s = optim.get("sgd", 0.4, packed=True, impl="pallas")
    opt_r = optim.get("sgd", 0.4, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=4)
    rnd_s = jax.jit(lsgd.make_local_round(quad_loss, opt_s, cfg,
                                          layout=layout, exchange=ex,
                                          shardexec=sexec))
    rnd_r = jax.jit(lsgd.make_local_round(quad_loss, opt_r, cfg,
                                          layout=layout, exchange=ex))
    ss = lsgd.init_state(params, opt_s, n_groups=G, layout=layout,
                         exchange=ex)
    sr = lsgd.init_state(params, opt_r, n_groups=G, layout=layout,
                         exchange=ex)
    for _ in range(80):
        ss, ms = rnd_s(ss, batch)
        sr, mr = rnd_r(sr, batch)
    gs, gr = float(jnp.mean(ms["grad_sq"])), float(jnp.mean(mr["grad_sq"]))
    assert gs < 1e-10 and gr < 1e-10, (gs, gr)
    assert gs <= 10 * gr + 1e-12, (gs, gr)
    # the residual stayed shard-pure zero in the pad all along
    res = np.asarray(ss["comm"]["codec"]["params"]["residual"])
    np.testing.assert_array_equal(res[:, layout.size:], 0.0)


@needs8
def test_sharded_topk_ring_runs_and_contracts(key):
    """Per-hop sharded top-k on a ring: finite, contracts disagreement
    (spectral gap survives the threshold codec), residual pad clean."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    x0 = packing.pack(lsgd.replicate(params, G), layout)
    mask = (jnp.arange(layout.padded) < layout.size).astype(jnp.float32)
    x = x0 + jax.random.normal(key, x0.shape) * mask
    ex = comm.get_exchange("ring", "topk", G, mix_rounds=4,
                           topk_frac=0.25)
    out, st = jax.jit(sexec.exchange(ex, layout))(x, x0, ex.init(x0))
    o = np.asarray(out)
    assert np.isfinite(o).all()
    dis_in = float(np.abs(np.asarray(x) - np.asarray(x).mean(0)).max())
    dis_out = float(np.abs(o - o.mean(0)).max())
    assert dis_out < 0.9 * dis_in
    np.testing.assert_array_equal(
        np.asarray(st["codec"]["params"]["residual"])[:, layout.size:], 0.0)


@needs8
def test_builder_threads_topk_sharded(key):
    """The mesh builder accepts codec=topk on a sharded mesh now (the
    §9 refusal is lifted) and the comm state carries the sharded
    residual with the buffer's spec."""
    from repro.configs.base import InputShape, get_config
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    mesh = mesh8()
    shape = InputShape(name="tiny", kind="train", global_batch=8,
                       seq_len=8)
    built = build_train_step(cfg, shape, mesh, t_inner=2, packed=True,
                             codec="topk", impl="pallas")
    assert built.meta["sharded"] is True
    state_abs, _ = built.args
    r = state_abs["comm"]["codec"]["params"]["residual"]
    assert r.shape == state_abs["params"].shape
    # the EF residual SHARDS like the params (a lead-only spec would
    # reshard the O(Np) residual through every round's shard_map call)
    psh = built.in_shardings[0]["params"]
    rsh = built.in_shardings[0]["comm"]["codec"]["params"]["residual"]
    assert rsh.shard_shape(tuple(r.shape)) \
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
        f"8-device exchange-engine suite failed:\n{r.stdout[-4000:]}"
        f"\n{r.stderr[-2000:]}")
