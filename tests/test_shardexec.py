"""Sharded execution layer (sharding/shardexec.py + packing.ShardedLayout).

Acceptance-critical invariants (ISSUE 3 / DESIGN.md §9):
  * ShardedLayout pads to a shard*chunk multiple; pack/unpack round-trip
    through the padded buffer; the pad region stays zero,
  * on a forced 8-device host mesh the sharded packed round (Pallas
    kernels inside shard_map on shard-local buffers) matches the
    replicated path <= 1e-5 rel for sgd/momentum/adamw x {server, ring}
    x {fp32, int8},
  * int8 per-chunk scales are shard-local: the sharded exchange is
    BIT-identical to the replicated one (same noise, same chunk geometry),
  * the packed train-step builder unpins impl on sharded meshes, donates
    the sharded state (memory analysis shows the aliasing), and refuses
    the combos that cannot shard (topk, pallas-on-replicated-GSPMD).

Most tests need 8 devices. Under the plain 1-device tier-1 run,
``test_suite_under_forced_8_devices`` re-runs this module in a child
process with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
device count is locked at jax init, so it cannot be flipped in-process);
under CI's forced-8-device job the tests simply run directly.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, optim
from repro.core import localsgd as lsgd
from repro.optim import packing
from repro.sharding import shardexec as shx

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


def mesh8(shape=(4, 2), axes=("data", "model")):
    from jax.sharding import Mesh
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


# ---------------------------------------------------------------------------
# ShardedLayout: padding, round-trip, alignment (no devices needed)
# ---------------------------------------------------------------------------


def test_shard_layout_roundtrip_with_padding(key):
    params, _ = make_problem(key)
    base = packing.layout_of(params)
    layout = packing.shard_layout(base, n_shards=2, align=256)
    assert layout.padded % (2 * 256) == 0
    assert layout.shard_size % 256 == 0
    assert layout.padded >= base.size and layout.size == base.size
    buf = packing.pack(params, layout)
    assert buf.shape == (layout.padded,)
    # the pad region is exactly zero and unpack ignores it
    np.testing.assert_array_equal(np.asarray(buf[base.size:]), 0.0)
    back = packing.unpack(buf, layout)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b)
    # grouped packing pads every group's row
    tree_G = lsgd.replicate(params, 3)
    buf_G = packing.pack(tree_G, layout)
    assert buf_G.shape == (3, layout.padded)
    assert layout.abstract((3,)).shape == (3, layout.padded)


def test_shard_layout_pad_stays_zero_through_updates(key):
    """The pad region is a fixed point of every packed optimizer: zero
    params + zero grads + zero moments stay exactly zero, so padding
    never bleeds into real elements over a round."""
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params), 2, align=64)
    buf = packing.pack(params, layout)
    g = packing.pack(jax.tree.map(jnp.ones_like, params), layout)
    for name in ("sgd", "momentum", "adamw"):
        opt = optim.packed(name, 0.1, impl="jnp")
        state = opt.init(buf)
        b = buf
        for _ in range(3):
            b, state = opt.step(b, g, state)
        np.testing.assert_array_equal(np.asarray(b[layout.size:]), 0.0)


def test_plan_and_layout_guards(key):
    params, _ = make_problem(key)
    base = packing.layout_of(params)
    # plan_for on a 1-device mesh: nothing to shard over
    from jax.sharding import Mesh
    m1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
              ("data", "model"))
    assert shx.plan_for(m1) is None
    with pytest.raises(ValueError):
        shx.plan_for(m1, require=True)
    # a plain Layout is refused by sharded execution
    fake = shx.ShardExec(mesh=m1, group_axes=("data",),
                         shard_axes=("model",))
    with pytest.raises(ValueError):
        fake.check_layout(base)
    # shard-count mismatch is refused
    with pytest.raises(ValueError):
        fake.check_layout(packing.shard_layout(base, 4))
    # chunk misalignment is refused (scales must stay shard-local)
    bad = packing.shard_layout(base, 1, align=8)
    with pytest.raises(ValueError):
        fake.check_layout(bad, chunk=256)


def test_sharded_path_refusals(key):
    """The combos that stay replicated-only: a downlink codec (its
    broadcast-reference state is not threaded through shard_map) and
    async+topk (mirrors the replicated refusal). topk itself is NO
    LONGER refused — it runs sharded via the distributed threshold
    selection (DESIGN.md §11, tests/test_exchange_engine.py)."""
    params, _ = make_problem(key)
    from jax.sharding import Mesh
    m1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
              ("data", "model"))
    fake = shx.ShardExec(mesh=m1, group_axes=("data",),
                         shard_axes=("model",))
    layout = packing.shard_layout(packing.layout_of(params), 1)
    ex = comm.get_exchange("server", "topk", G)
    fake.exchange(ex, layout)   # builds — topk shards now
    ex_d = comm.get_exchange("server", "fp32", G, downlink_codec="bf16")
    with pytest.raises(NotImplementedError):
        fake.exchange(ex_d, layout)
    import dataclasses as _dc
    ex_async = _dc.replace(comm.get_exchange("async_stale", "fp32", G,
                                             staleness=1),
                           codec=comm.get_codec("topk"))
    with pytest.raises(NotImplementedError):
        fake.exchange(ex_async, layout)
    with pytest.raises(ValueError):
        _dc.replace(fake, hop_impl="bogus")._hop_fn(
            np.eye(G, dtype=np.float32), "data")


def test_shardexec_needs_packed_path(key):
    params, _ = make_problem(key)
    from jax.sharding import Mesh
    m1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
              ("data", "model"))
    fake = shx.ShardExec(mesh=m1, group_axes=("data",),
                         shard_axes=("model",))
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    with pytest.raises(ValueError):
        lsgd.make_local_round(quad_loss, optim.sgd(0.1), cfg,
                              shardexec=fake)


def test_impl_errors_are_clear():
    """No silent fallbacks / bare asserts: unknown impl names raise
    ValueError; a pytree optimizer refuses impl= outright (the fused
    kernels only exist packed); pallas on an unsupported backend raises
    NotImplementedError (cpu/tpu are supported, so only the message path
    is checkable here)."""
    from repro.kernels import pallas_supported, resolve_impl

    with pytest.raises(ValueError):
        resolve_impl("cuda")
    with pytest.raises(ValueError):
        optim.get("sgd", 0.1, impl="pallas")          # pytree + impl
    assert pallas_supported()                          # cpu container
    assert resolve_impl("pallas") == "pallas"          # interpret mode ok
    assert resolve_impl("auto") == "jnp"               # cpu default


def test_packed_sync_refuses_fsdp_mesh_and_pytree_refuses_impl():
    """Two more no-silent-path guards: packed sync on an fsdp mesh must
    refuse (its buffer stays replicated — recording that profile on a
    mesh built for sharding would mislead), and the pytree (non-packed)
    builder refuses impl= outright."""
    from jax.sharding import Mesh
    from repro.configs.base import InputShape, get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    shape = InputShape(name="tiny", kind="train", global_batch=4,
                       seq_len=8)
    mesh_f = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                  ("data", "fsdp", "model"))
    with pytest.raises(NotImplementedError):
        build_train_step(cfg, shape, mesh_f, mode="sync", packed=True)
    with pytest.raises(ValueError):
        build_train_step(cfg, shape, make_local_mesh(1, 1),
                         packed=False, impl="pallas")


def test_pallas_impl_refused_on_replicated_multidevice_mesh():
    """No silent jnp fallback: an explicit impl='pallas' on a
    multi-device mesh with no in-group shard axis must raise (a
    pallas_call there is not GSPMD-partitionable)."""
    from repro.launch.steps import _packed_impl

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 1}

        class devices:
            size = 4

    with pytest.raises(NotImplementedError):
        _packed_impl("pallas", FakeMesh(), None)
    assert _packed_impl("auto", FakeMesh(), None) == "jnp"


# ---------------------------------------------------------------------------
# 8-device mesh: parity, exactness, builder, donation
# ---------------------------------------------------------------------------


@needs8
@pytest.mark.parametrize("opt_name", ["sgd", "momentum", "adamw"])
@pytest.mark.parametrize("topo", ["server", "ring"])
@pytest.mark.parametrize("codec", ["fp32", "int8"])
def test_sharded_round_parity(opt_name, topo, codec, key):
    """THE acceptance gate: multi-round sharded packed rounds (Pallas
    kernels in shard_map on shard-local buffers) match the replicated
    path on the same padded layout to <= 1e-5 rel."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    assert sexec.n_shards == 2 and sexec.group_axes == ("data",)
    params, batch = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    ex = comm.get_exchange(topo, codec, G, mix_rounds=2, impl="jnp")
    opt_s = optim.get(opt_name, 0.05, packed=True, impl="pallas")
    opt_r = optim.get(opt_name, 0.05, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=3, metrics="traj")
    rnd_s = jax.jit(lsgd.make_local_round(quad_loss, opt_s, cfg,
                                          layout=layout, exchange=ex,
                                          shardexec=sexec))
    rnd_r = jax.jit(lsgd.make_local_round(quad_loss, opt_r, cfg,
                                          layout=layout, exchange=ex))
    ss = lsgd.init_state(params, opt_s, n_groups=G, layout=layout,
                         exchange=ex)
    sr = lsgd.init_state(params, opt_r, n_groups=G, layout=layout,
                         exchange=ex)
    for _ in range(3):
        ss, ms = rnd_s(ss, batch)
        sr, mr = rnd_r(sr, batch)
    scale = float(jnp.max(jnp.abs(sr["params"]))) + 1e-12
    err = float(jnp.max(jnp.abs(ss["params"] - sr["params"]))) / scale
    assert err <= 1e-5, (opt_name, topo, codec, err)
    # opt-state moments agree too (they follow the topology sharded)
    for k in ss["opt"]:
        if k == "count":
            continue
        m_scale = float(jnp.max(jnp.abs(sr["opt"][k]))) + 1e-12
        m_err = float(jnp.max(jnp.abs(ss["opt"][k] - sr["opt"][k])))
        assert m_err / m_scale <= 1e-5, (opt_name, topo, codec, k)
    # traj metrics: the sq_norm psum path matches the flat reduction
    np.testing.assert_allclose(np.asarray(ms["grad_sq_traj"]),
                               np.asarray(mr["grad_sq_traj"]),
                               rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(np.asarray(ms["loss"]),
                               np.asarray(mr["loss"]), rtol=1e-4)


@needs8
def test_sharded_int8_codec_bit_identical(key):
    """Shard-local chunk scales: each shard's rows are whole chunks of
    the full buffer and the noise is sliced from the SAME full-shape
    draw, so the decoded payload is bit-for-bit the replicated one —
    slicing rows before or after compress_rows commutes exactly."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    codec = comm.get_codec("int8", impl="jnp")
    delta = jax.random.normal(key, (G, layout.padded)) * 0.1
    rows = packing.chunk_rows(delta, codec.chunk)
    u = codec.noise(jnp.zeros((), jnp.int32), rows.shape)
    full = np.asarray(codec.compress_rows(rows, u)
                      .reshape(G, layout.padded))
    # shard-local: group g, shard s sees its own contiguous row block
    rs = layout.shard_size // codec.chunk          # rows per shard
    u_g = np.asarray(u).reshape(G, -1, codec.chunk)
    for g in range(G):
        for s in range(sexec.n_shards):
            loc = delta[g, s * layout.shard_size:
                        (s + 1) * layout.shard_size]
            got = codec.compress_rows(
                loc.reshape(-1, codec.chunk),
                jnp.asarray(u_g[g, s * rs:(s + 1) * rs]))
            np.testing.assert_array_equal(
                np.asarray(got).reshape(-1),
                full[g, s * layout.shard_size:(s + 1) * layout.shard_size])


@needs8
def test_sharded_int8_exchange_matches_replicated(key):
    """The full sharded exchange (quantize kernels in shard_map + psum
    mean) against the replicated exchange: identical codec bits, mixing
    differs only by collective reduction order (~1 ulp)."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, _ = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    ex = comm.get_exchange("server", "int8", G, impl="jnp")
    x0 = packing.pack(lsgd.replicate(params, G), layout)
    x = x0 + jax.random.normal(key, x0.shape) * 0.1
    state = ex.init(x0)
    fn = sexec.exchange(ex, layout)
    out_s, st_s = jax.jit(fn)(x, x0, state)
    out_r, st_r = jax.jit(ex.params)(x, x0, state)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               rtol=1e-6, atol=1e-7)
    assert int(st_s["codec"]["params"]["count"]) \
        == int(st_r["codec"]["params"]["count"]) == 1


@needs8
def test_sharded_async_stale_parity(key):
    """async_stale on the sharded path: the staleness buffer shards like
    the params; the masked refresh + psum-mean matches the replicated
    path."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, batch = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    ex = comm.get_exchange("async_stale", "fp32", G, staleness=1)
    opt_s = optim.get("sgd", 0.05, packed=True, impl="pallas")
    opt_r = optim.get("sgd", 0.05, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2,
                              average_opt_state=False)
    rnd_s = jax.jit(lsgd.make_local_round(quad_loss, opt_s, cfg,
                                          layout=layout, exchange=ex,
                                          shardexec=sexec))
    rnd_r = jax.jit(lsgd.make_local_round(quad_loss, opt_r, cfg,
                                          layout=layout, exchange=ex))
    ss = lsgd.init_state(params, opt_s, n_groups=G, layout=layout,
                         exchange=ex)
    sr = lsgd.init_state(params, opt_r, n_groups=G, layout=layout,
                         exchange=ex)
    for _ in range(4):
        ss, _ = rnd_s(ss, batch)
        sr, _ = rnd_r(sr, batch)
    scale = float(jnp.max(jnp.abs(sr["params"]))) + 1e-12
    assert float(jnp.max(jnp.abs(ss["params"] - sr["params"]))) / scale \
        <= 1e-5
    assert int(ss["comm"]["round"]) == 4
    np.testing.assert_allclose(np.asarray(ss["comm"]["pushed"]),
                               np.asarray(sr["comm"]["pushed"]),
                               rtol=1e-5, atol=1e-7)


@needs8
def test_sharded_parity_fsdp_mesh(key):
    """A (data=2, fsdp=2, model=2) mesh: the buffer shards 4-way over
    BOTH in-group axes; parity holds."""
    mesh = mesh8((2, 2, 2), ("data", "fsdp", "model"))
    sexec = shx.plan_for(mesh)
    assert sexec.shard_axes == ("fsdp", "model") and sexec.n_shards == 4
    params, batch = make_problem(key, g=2)
    layout = packing.shard_layout(packing.layout_of(params), 4)
    opt_s = optim.get("momentum", 0.05, packed=True, impl="pallas")
    opt_r = optim.get("momentum", 0.05, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=2, inner_steps=2)
    ex = comm.get_exchange("server", "fp32", 2)
    rnd_s = jax.jit(lsgd.make_local_round(quad_loss, opt_s, cfg,
                                          layout=layout, exchange=ex,
                                          shardexec=sexec))
    rnd_r = jax.jit(lsgd.make_local_round(quad_loss, opt_r, cfg,
                                          layout=layout, exchange=ex))
    ss = lsgd.init_state(params, opt_s, n_groups=2, layout=layout)
    sr = lsgd.init_state(params, opt_r, n_groups=2, layout=layout)
    ss, _ = rnd_s(ss, batch)
    sr, _ = rnd_r(sr, batch)
    scale = float(jnp.max(jnp.abs(sr["params"]))) + 1e-12
    assert float(jnp.max(jnp.abs(ss["params"] - sr["params"]))) / scale \
        <= 1e-5


@needs8
def test_sync_packed_impl_gate_on_mesh():
    """sync never enters shard_map, so even on a sharded-capable mesh a
    packed sync step refuses impl='pallas' (auto resolves to jnp) — the
    gate considers mode, not just mesh shape."""
    from repro.configs.base import InputShape, get_config
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    mesh = mesh8()
    shape = InputShape(name="tiny", kind="train", global_batch=8,
                       seq_len=8)
    with pytest.raises(NotImplementedError):
        build_train_step(cfg, shape, mesh, mode="sync", packed=True,
                         impl="pallas")
    built = build_train_step(cfg, shape, mesh, mode="sync", packed=True)
    assert built.meta["impl"] == "jnp"


@needs8
def test_build_packed_train_step_sharded(key):
    """The mesh builder takes the sharded path (impl unpinned): Pallas
    fused update + int8 quantize kernels inside shard_map, sharded
    shardings on state, donation aliasing in the memory analysis, and
    per-device state bytes cut by n_shards."""
    from repro.configs.base import InputShape, get_config
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    mesh = mesh8()
    shape = InputShape(name="tiny", kind="train", global_batch=8,
                       seq_len=8)
    built = build_train_step(cfg, shape, mesh, t_inner=2,
                             opt_name="adamw", packed=True,
                             codec="int8", impl="pallas")
    meta = built.meta
    assert meta["sharded"] is True and meta["n_shards"] == 2
    assert meta["impl"] == "pallas"
    assert meta["n_flat_padded"] % (2 * 256) == 0
    assert meta["wire_bytes_per_round"] == (meta["wire_bytes_up_per_round"]
                                            + meta["wire_bytes_down_per_"
                                                   "round"])
    state_abs, _ = built.args
    assert state_abs["params"].shape == (4, meta["n_flat_padded"])
    # params shard over BOTH the group and the model axes
    psh = built.in_shardings[0]["params"]
    shard_shape = psh.shard_shape(tuple(state_abs["params"].shape))
    assert shard_shape == (1, meta["n_flat_padded"] // 2)
    with mesh:
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=built.donate_argnums)
        compiled = jitted.lower(*built.args).compile()
    ma = compiled.memory_analysis()
    if ma is not None and hasattr(ma, "alias_size_in_bytes"):
        # params + m + v donated in place: at least 3 G-sharded buffers
        state_bytes = 3 * 4 * state_abs["params"].size
        assert ma.alias_size_in_bytes >= state_bytes // mesh.devices.size


@needs8
def test_sharded_matches_replicated_builder_end_to_end(key):
    """Same config: the sharded builder's round (2 shards per group,
    Pallas) and the group-only placement (a data-axis-only mesh: one
    whole buffer per device, jnp) produce the same server params after a
    round, <= 1e-5 rel — the builder-level version of the parity gate."""
    from jax.sharding import Mesh
    from repro.configs.base import InputShape, get_config
    from repro.launch.steps import build_train_step

    cfg = get_config("paper-mlp").reduced()
    shape = InputShape(name="tiny", kind="train", global_batch=4,
                       seq_len=8)
    mesh_s = mesh8()
    mesh_r = Mesh(np.array(jax.devices()[:4]).reshape(4, 1),
                  ("data", "model"))
    outs = {}
    for tag, mesh, impl in (("sharded", mesh_s, "pallas"),
                            ("replicated", mesh_r, "jnp")):
        built = build_train_step(cfg, shape, mesh, t_inner=2,
                                 opt_name="sgd", packed=True, impl=impl)
        assert built.meta["sharded"]
        assert built.meta["n_shards"] == (2 if tag == "sharded" else 1)
        state_abs, batch_abs = built.args
        rng = np.random.RandomState(0)
        from repro.models import build_model
        model = build_model(cfg, schedule="rect")
        params = model.init(jax.random.PRNGKey(0))
        layout = packing.shard_layout(packing.layout_of(params),
                                      built.meta["n_shards"])
        opt = optim.get("sgd", 1e-3, packed=True, impl=impl)
        state = lsgd.init_state(params, opt, n_groups=4, layout=layout)
        batch = {"tokens": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (4, 1, 8)), jnp.int32)}
        with mesh:
            jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                             out_shardings=built.out_shardings,
                             donate_argnums=built.donate_argnums)
            new_state, _ = jitted(state, batch)
        outs[tag] = np.asarray(
            jax.tree.leaves(lsgd.server_params(new_state,
                                               layout=layout))[0])
    np.testing.assert_allclose(outs["sharded"], outs["replicated"],
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# multi-stream payloads on the sharded path (DESIGN.md §10)
# ---------------------------------------------------------------------------


@needs8
@pytest.mark.parametrize("opt_name", ["momentum", "adamw"])
@pytest.mark.parametrize("topo", ["server", "ring"])
def test_sharded_stream_parity_moment_codec(opt_name, topo, key):
    """The §10 sharded parity gate: moments ride their own int8 codec
    inside the shard_map exchange — multi-round sharded packed rounds
    (Pallas kernels) match the replicated path <= 1e-5 rel on params AND
    every moment stream (the int8 noise is per-stream, generated outside
    at full rows shape, so the codec bits are identical)."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, batch = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    ex = comm.get_exchange(topo, "int8", G, mix_rounds=2, impl="jnp",
                           moment_codec="int8")
    opt_s = optim.get(opt_name, 0.03, packed=True, impl="pallas")
    opt_r = optim.get(opt_name, 0.03, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=3, metrics="traj")
    rnd_s = jax.jit(lsgd.make_local_round(quad_loss, opt_s, cfg,
                                          layout=layout, exchange=ex,
                                          shardexec=sexec))
    rnd_r = jax.jit(lsgd.make_local_round(quad_loss, opt_r, cfg,
                                          layout=layout, exchange=ex))
    ss = lsgd.init_state(params, opt_s, n_groups=G, layout=layout,
                         exchange=ex)
    sr = lsgd.init_state(params, opt_r, n_groups=G, layout=layout,
                         exchange=ex)
    assert set(ss["comm"]["codec"]) == {"params"} | set(opt_s.moment_keys)
    for _ in range(3):
        ss, ms = rnd_s(ss, batch)
        sr, mr = rnd_r(sr, batch)
    scale = float(jnp.max(jnp.abs(sr["params"]))) + 1e-12
    err = float(jnp.max(jnp.abs(ss["params"] - sr["params"]))) / scale
    assert err <= 1e-5, (opt_name, topo, err)
    for k in opt_s.moment_keys:
        m_scale = float(jnp.max(jnp.abs(sr["opt"][k]))) + 1e-12
        m_err = float(jnp.max(jnp.abs(ss["opt"][k] - sr["opt"][k])))
        assert m_err / m_scale <= 1e-5, (opt_name, topo, k)
        # per-stream rng counters advanced identically on both paths
        np.testing.assert_array_equal(
            np.asarray(ss["comm"]["codec"][k]["count"]),
            np.asarray(sr["comm"]["codec"][k]["count"]))
    np.testing.assert_allclose(np.asarray(ms["grad_sq_traj"]),
                               np.asarray(mr["grad_sq_traj"]),
                               rtol=1e-4, atol=1e-8)


@needs8
def test_sharded_fp32_moments_bit_exact_vs_mix(key):
    """§10 bit-exactness on the sharded path: with moment_codec=fp32 the
    stream exchange's moment mixing is the SAME psum-mean ops as the old
    shardexec.mix — compare the round's moments against mixing the
    no-comm locals by hand, bit for bit."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, batch = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    opt = optim.get("momentum", 0.05, packed=True, impl="pallas")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    ex = comm.get_exchange("server", "fp32", G)
    ex_none = comm.get_exchange("none", "fp32", G)
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex,
                                        shardexec=sexec))
    rnd_none = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                             layout=layout,
                                             exchange=ex_none,
                                             shardexec=sexec))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout)
    locals_, _ = rnd_none(jax.tree.map(jnp.copy, st), batch)
    got, _ = rnd(st, batch)
    mix = sexec.mix(ex)
    np.testing.assert_array_equal(np.asarray(got["opt"]["mu"]),
                                  np.asarray(jax.jit(mix)(
                                      locals_["opt"]["mu"])))
    np.testing.assert_array_equal(np.asarray(got["params"]),
                                  np.asarray(jax.jit(mix)(
                                      locals_["params"])))


@needs8
def test_sharded_async_avg_opt_parity(key):
    """async_stale + average_opt_state=True on the sharded path (§10):
    per-stream staleness buffers shard like the params; masked refresh +
    psum-mean of params AND moments match the replicated path."""
    mesh = mesh8()
    sexec = shx.plan_for(mesh)
    params, batch = make_problem(key)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    ex = comm.get_exchange("async_stale", "fp32", G, staleness=1)
    opt_s = optim.get("momentum", 0.05, packed=True, impl="pallas")
    opt_r = optim.get("momentum", 0.05, packed=True, impl="jnp")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)  # avg_opt on
    rnd_s = jax.jit(lsgd.make_local_round(quad_loss, opt_s, cfg,
                                          layout=layout, exchange=ex,
                                          shardexec=sexec))
    rnd_r = jax.jit(lsgd.make_local_round(quad_loss, opt_r, cfg,
                                          layout=layout, exchange=ex))
    ss = lsgd.init_state(params, opt_s, n_groups=G, layout=layout,
                         exchange=ex)
    sr = lsgd.init_state(params, opt_r, n_groups=G, layout=layout,
                         exchange=ex)
    assert set(ss["comm"]["pushed_opt"]) == {"mu"}
    for _ in range(4):
        ss, _ = rnd_s(ss, batch)
        sr, _ = rnd_r(sr, batch)
    for name, a, b in (("params", ss["params"], sr["params"]),
                       ("mu", ss["opt"]["mu"], sr["opt"]["mu"]),
                       ("pushed", ss["comm"]["pushed"],
                        sr["comm"]["pushed"]),
                       ("pushed_mu", ss["comm"]["pushed_opt"]["mu"],
                        sr["comm"]["pushed_opt"]["mu"])):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) / scale <= 1e-5, name
    assert int(ss["comm"]["round"]) == 4


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
        f"8-device shardexec suite failed:\n{r.stdout[-4000:]}"
        f"\n{r.stderr[-2000:]}")
