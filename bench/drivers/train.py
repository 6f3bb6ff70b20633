"""Training traffic: the program's packed local-SGD round, driven the way
``launch/train.py`` composes it, for a fixed time.

Set-up builds ONE jitted round with its state (weights from the seed,
made on the device), drives it through its first ``check_rounds`` rounds
(the first call compiles; those rounds' readings are what the check
compares), and hands the same round and state to the window. Each round
of the window draws a fresh (G, B, S) batch of token ids from the seed
and the round's index, runs the round and reads its loss on the host, as
the launcher does, but ``LAG`` rounds later, as an asynchronous logger
would: a host stall shorter than that many rounds (one of about a
second in some 40 s on a v5e host, PERF.md) then leaves the chip busy.
The window closes when the last round dispatched has returned its loss.
After the window the program's state is freed and the plain reference
(``refs/decoder.py``) repeats the first rounds from the same seed.

Traffic keys: groups G, local_steps T, per_group_batch B, seq_len S,
optimizer, lr, beta, exchange, codec, check_rounds.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

import common

LAG = 3     # rounds in flight before the oldest one's loss is read


def _leaf_norms(flat_tree):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(flat_tree)


def gap_worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    """max over leaves of |norm_prog - norm_ref| / max(norm_ref of the
    leaf, median leaf's norm_ref)."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the training check compares (see PERF.md)."""
    g = ref["mu1"]
    med = float(np.median(list(g.values())))
    # leaves whose reference gradient (as the optimizer got it after the
    # first round) is nought to rounding move by round-off alone: they
    # are left out of the change by this rule
    moving = {k for k, v in g.items() if v >= 1e-3 * med}
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    return {"loss_gap": float(np.max(np.abs(lp - lr))),
            "mu1_gap": gap_worst_leaf(prog["mu1"], ref["mu1"]),
            "dparams_gap": gap_worst_leaf(prog["dparams"], ref["dparams"],
                                          moving)}


def read_loss(loss):
    with common.annotate("read_loss"):
        return np.asarray(loss)


class Round:
    """The program's round, its state and its feed, built from a cell."""

    def __init__(self, cell: dict, seed: int, devs):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        common.src_on_path()
        from repro import comm, optim
        from repro.core import localsgd as lsgd
        from repro.models import build_model
        from repro.optim import packing
        from refs import decoder as ref

        tf = cell["traffic_file"]
        arch = cell["config_file"]["arch"]
        G, B, S = tf["groups"], tf["per_group_batch"], tf["seq_len"]
        cfg = common.arch_config(cell["config_file"])
        model = build_model(cfg, schedule="rect")
        flat = common.make_weights(ref.param_specs(arch), seed)
        params = common.nest(flat)
        want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if jax.tree.map(lambda x: (x.shape, x.dtype), want) != got:
            raise SystemExit("bench: the weights' tree does not match the "
                             "program's parameters")
        self.p0 = flat
        layout = packing.layout_of(params)
        opt = optim.get(tf["optimizer"], tf["lr"], packed=True,
                        beta=tf["beta"])
        exch = comm.get_exchange(tf["exchange"], tf["codec"], G)
        avg = exch.supports_opt_state_averaging
        lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=tf["local_steps"],
                                   metrics="final", average_opt_state=avg)
        sexec = mesh = None
        if len(devs) > 1:
            from repro.sharding import shardexec as shx
            mesh = Mesh(np.array(devs[:G]).reshape(G, 1), ("data", "model"))
            sexec = shx.plan_for(mesh, require=True)
            layout = packing.shard_layout(layout, sexec.n_shards)
        self.layout = layout
        rnd = lsgd.make_local_round(model.loss, opt, lcfg, layout=layout,
                                    exchange=exch, shardexec=sexec)
        self.round = jax.jit(rnd, donate_argnums=(0,))
        state = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                                exchange=exch, average_opt_state=avg)
        del params
        batch_sh = None
        if mesh is not None:
            buf_sh = NamedSharding(mesh, sexec.buf_spec())
            rep_sh = NamedSharding(mesh, P())
            state = jax.tree.map(
                lambda x: jax.device_put(
                    x, buf_sh if (x.ndim == 2
                                  and x.shape[-1] == layout.padded)
                    else rep_sh), state)
            batch_sh = NamedSharding(mesh, sexec.group_spec())
        self.state = state
        key = jax.random.fold_in(common.base_key(seed), common.LANE_DATA)
        V = arch["vocab_size"]

        def gen(r):
            k = jax.random.fold_in(key, r)
            return jax.random.randint(k, (G, B, S), 0, V, jnp.int32)

        self.gen = jax.jit(gen, out_shardings=batch_sh)
        self.unpack0 = jax.jit(lambda buf: common.flatten(
            packing.unpack(buf[0], layout)))
        self.r = 0

    def dispatch(self):
        """Start one round on the next batch; returns its (G,) losses on
        the device."""
        with common.annotate("data"):
            batch = {"tokens": self.gen(np.int32(self.r))}
        with common.annotate("round"):
            self.state, m = self.round(self.state, batch)
        self.r += 1
        return m["loss"]

    def step(self):
        """One round, and its (G,) losses read on the host."""
        return read_loss(self.dispatch())

    def warm(self, n: int) -> dict:
        """The first ``n`` rounds, with the readings the check compares."""
        out = {"loss": []}
        for r in range(n):
            out["loss"].append(self.step().tolist())
            if r == 0:
                mu = self.unpack0(self.state["opt"]["mu"])
                out["mu1"] = {k: float(v)
                              for k, v in _leaf_norms(mu).items()}
                del mu
        import jax
        pn = self.unpack0(self.state["params"])
        d = jax.tree.map(lambda a, b: a - b, pn, self.p0)
        out["dparams"] = {k: float(v) for k, v in _leaf_norms(d).items()}
        del pn, d
        self.p0 = None
        return out


def reference(cell: dict, seed: int, gen, rounds: int, mode: str = "f32",
              fault: str = "") -> dict:
    """The reference's readings of the first ``rounds`` rounds."""
    import jax
    import jax.numpy as jnp
    from refs import decoder as ref
    tf = cell["traffic_file"]
    arch = cell["config_file"]["arch"]
    dev = jax.devices()[0]
    batches = [jax.device_put(jnp.asarray(np.asarray(gen(np.int32(r)))),
                              dev) for r in range(rounds)]
    params = common.nest(common.make_weights(ref.param_specs(arch), seed))
    return ref.run_rounds(params, batches, arch, tf["lr"], tf["beta"],
                          tf["local_steps"], mode=mode, fault=fault)


def run(cell, seed, seconds, trace_dir, devs, counter):
    import jax
    tf = cell["traffic_file"]
    rnd = Round(cell, seed, devs)
    prog = rnd.warm(tf["check_rounds"])
    # every shape is warm: the window compiles nothing
    if trace_dir:
        common.start_trace(trace_dir)
    counter.armed = True
    t_start = time.perf_counter()
    n, bad, done = 0, 0, 0
    pending = collections.deque()
    with common.annotate("window"):
        while True:
            pending.append(rnd.dispatch())
            n += 1
            if len(pending) > LAG:
                bad += int(not np.all(np.isfinite(read_loss(
                    pending.popleft()))))
                done += 1
            # stop when the rounds in flight would end the window
            el = time.perf_counter() - t_start
            if done and el + len(pending) * el / done >= seconds:
                break
        while pending:
            bad += int(not np.all(np.isfinite(read_loss(pending.popleft()))))
    t_end = time.perf_counter()
    counter.armed = False
    if trace_dir:
        jax.profiler.stop_trace()
    device = common.device_record(devs)
    gen, n_padded = rnd.gen, rnd.layout.padded
    rnd.state = None
    del rnd
    gc.collect()
    window = t_end - t_start
    chips = len(devs)
    tokens = n * tf["groups"] * tf["per_group_batch"] * tf["seq_len"] \
        * tf["local_steps"]
    t_ref = time.perf_counter()
    ref_out = reference(cell, seed, gen, tf["check_rounds"])
    checks = compare(prog, ref_out)
    ref_out["seconds"] = time.perf_counter() - t_ref
    return {
        "t_window": t_start,
        "attempted": n, "failed": bad,
        "e2e": {"train_tokens_per_s": tokens / window / chips},
        "device": device,
        "checks": checks,
        "ctx": {"kind": "train", "arch": cell["config_file"]["arch"],
                "traffic": tf, "chips": chips, "rounds": n,
                "window_s": window,
                "tokens_per_s_per_chip": tokens / window / chips,
                "groups_per_chip": tf["groups"] // chips,
                "n_padded": n_padded},
        "readings": {"program": prog, "reference": ref_out},
    }

