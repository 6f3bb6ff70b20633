"""Checkpoint roundtrip."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io


def test_roundtrip(tmp_path, key):
    tree = {
        "params": {"w": jax.random.normal(key, (4, 3)),
                   "b": jnp.zeros((3,), jnp.bfloat16)},
        "opt": {"count": jnp.asarray(7, jnp.int32),
                "mu": {"w": jnp.ones((4, 3))}},
    }
    path = str(tmp_path / "ckpt")
    io.save(path, tree, metadata={"round": 12, "arch": "qwen3-32b"})
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    loaded = io.load(path, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    md = io.load_metadata(path)
    assert md["round"] == 12 and md["arch"] == "qwen3-32b"


def test_roundtrip_list_pytree(tmp_path, key):
    tree = [jnp.arange(5), {"x": jnp.ones((2, 2))}]
    path = str(tmp_path / "ckpt2")
    io.save(path, tree)
    loaded = io.load(path, tree)
    np.testing.assert_allclose(loaded[0], tree[0])
    np.testing.assert_allclose(loaded[1]["x"], tree[1]["x"])


def test_roundtrip_packed_state_with_comm_streams(tmp_path, key):
    """The full packed train state survives: params/moment stream buffers,
    per-stream codec state (rng counters, nested under comm/codec/<stream>),
    per-stream async staleness buffers (pushed + pushed_opt/<stream>), and
    the round counter — then training RESUMES bit-exactly (DESIGN.md §10:
    the comm state is part of the algorithm, not a cache)."""
    import jax.numpy as jnp

    from repro import comm, optim
    from repro.core import localsgd as lsgd
    from repro.optim import packing

    G = 4
    ks = jax.random.split(key, 3)
    A = jax.random.normal(ks[0], (G, 4, 6))
    batch = {"A": A, "b": jax.random.normal(ks[1], (G, 4))}
    params = {"w": jax.random.normal(ks[2], (6,))}
    layout = packing.layout_of(params)

    def loss(p, b):
        r = b["A"] @ p["w"] - b["b"]
        return 0.5 * jnp.sum(r ** 2)

    opt = optim.packed("adamw", 0.05, impl="jnp")
    ex = comm.get_exchange("async_stale", "int8", G, staleness=1,
                           moment_codec="int8")
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=2)
    rnd = jax.jit(lsgd.make_local_round(loss, opt, cfg, layout=layout,
                                        exchange=ex))
    st = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                         exchange=ex)
    for _ in range(3):
        st, _ = rnd(st, batch)
    # nested per-stream comm state is present and non-trivial
    assert set(st["comm"]) == {"codec", "pushed", "pushed_opt", "round"}
    assert set(st["comm"]["codec"]) == {"params", "m", "v"}
    assert set(st["comm"]["pushed_opt"]) == {"m", "v"}

    path = str(tmp_path / "ckpt3")
    io.save(path, st, metadata={"round": 3, "comm": ex.name})
    like = jax.tree.map(jnp.zeros_like, st)
    loaded = io.load(path, like)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(st)[0],
            jax.tree_util.tree_flatten_with_path(loaded)[0]):
        assert ka == kb
        assert np.asarray(a).dtype == np.asarray(b).dtype, ka
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(ka))
    assert io.load_metadata(path)["comm"] == ex.name
    # resume parity: one more round from the loaded state must be
    # BIT-identical to continuing from the live state (the rng counters
    # and staleness buffers are what make this true)
    cont, mc = rnd(st, batch)
    res, mr = rnd(jax.tree.map(jnp.asarray, loaded), batch)
    for a, b in zip(jax.tree.leaves(cont), jax.tree.leaves(res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert rnd.wire_bytes(st) == rnd.wire_bytes(loaded)
