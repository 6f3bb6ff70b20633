"""A whole run of each cell after the look for chips (``run.measure``), at
a size a CPU run holds and with the timed path broken underneath from
outside the drivers: ``correct`` has to come out false for every fault
the cell can have, and true without one.

Training cells: a round that returns its state unchanged; half of each
group's rows left out (the loss's mean taken over the rest); the
exchange between groups left out. The serving driver (no serving cell
yet): a token altered where the decode step produces it."""
import copy

import jax
import pytest

import common
import run
from drivers import serve, train

SMALL_ARCH = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab_size=512)
# the serving driver's check at this size: a sound run's widest logit gap
# stays far below it, a token altered by one id lies far above it
SERVE_GAP_LIMIT = 0.15


def small(cell):
    cell = copy.deepcopy(cell)
    cell["config_file"]["arch"].update(SMALL_ARCH)
    if cell["config_file"]["arch"].get("n_experts"):
        cell["config_file"]["arch"].update(n_experts=4, top_k=2)
    tf = cell["traffic_file"]
    if tf["kind"] == "train":
        tf.update(per_group_batch=2, seq_len=32)
        cell["chips"] = 1
    else:
        tf.update(rate_per_s=20.0,
                  prompt_len={"median": 20, "sigma": 0.7, "min": 4,
                              "max": 64},
                  output_len={"median": 8, "sigma": 0.5, "min": 2,
                              "max": 16})
        tf["engine"].update(n_slots=4, page_size=8, max_prompt=64,
                            max_new=16)
    return cell


def cells(kind):
    return [w["name"] for w in common.spec()["workloads"]
            if common.cell_of(w["name"])["traffic_file"]["kind"] == kind]


def one_run(cell):
    return run.measure(cell, 2**31 + 9, 1.0, False, jax.devices()[:1])


def plant_train(monkeypatch, fault):
    """Break the program's round from outside the driver."""
    if fault == "noexchange":
        from repro import comm
        get = comm.get_exchange
        monkeypatch.setattr(comm, "get_exchange",
                            lambda name, codec, G: get("none", codec, G))
        return
    init = train.Round.__init__

    def broken(self, *a, **kw):
        init(self, *a, **kw)
        inner = self.round
        if fault == "frozen":
            def rnd(state, batch):
                return state, inner(state, batch)[1]
        else:   # "half"
            def rnd(state, batch):
                half = batch["tokens"].shape[1] // 2
                return inner(state, {"tokens": batch["tokens"][:, :half]})
        self.round = jax.jit(rnd, donate_argnums=(0,))
    monkeypatch.setattr(train.Round, "__init__", broken)


@pytest.mark.parametrize("name", cells("train"))
@pytest.mark.parametrize("fault", ["", "frozen", "half", "noexchange"])
def test_train_cell_faults(monkeypatch, name, fault):
    if fault:
        plant_train(monkeypatch, fault)
    rec = one_run(small(common.cell_of(name)))
    assert rec["correct"] is (fault == ""), rec["checks"]


def serve_cell():
    """The serving driver on the benchmark's configuration and the
    ``serve-chat`` traffic, as a later serving cell would compose it."""
    s = copy.deepcopy(common.spec())
    conf = s["configs"][0]["name"]
    s["workloads"].append({"name": "serve-test", "config": conf,
                           "traffic": "serve-chat", "chips": 1})
    cell = small(common.cell_of("serve-test", s))
    cell["limits"] = {"max_logit_gap": SERVE_GAP_LIMIT}
    return cell


@pytest.mark.parametrize("fault", ["", "token"])
def test_serve_driver_faults(monkeypatch, fault):
    if fault:
        build = serve.build_engine

        def broken(cell, seed):
            engine = build(cell, seed)
            step = engine.progs.step
            V = cell["config_file"]["arch"]["vocab_size"]

            def altered(*args):
                toks, pool = step(*args)
                return (toks + 1) % V, pool
            engine.progs.step = altered
            return engine
        monkeypatch.setattr(serve, "build_engine", broken)
    rec = one_run(serve_cell())
    assert rec["correct"] is (fault == ""), rec["checks"]
