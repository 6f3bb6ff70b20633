"""Named scopes from the program to the benchmark's readers.

* the packed round's optimized HLO carries every scope the per-layer
  readers ask for in its op metadata (``op_name``);
* ``bench/trace_scopes.py`` reads each device op's ``tf_op`` from the
  event metadata of a trace recorded on one TPU v5e, turns it into a
  scope path by its rule, and attributes ``trace_reduce``'s device time
  to the paths;
* the six scope readers of ``bench/metrics/`` each read a number from a
  trace of a 2-layer SmolLM2 round (G=4, T=2, 2 rounds) recorded on one
  TPU v5e with ``bench/tools/round_trace.py``.
"""
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import comm, optim
from repro.configs.base import ArchConfig
from repro.core import localsgd as lsgd
from repro.models import build_model
from repro.optim import packing

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
import trace_reduce as tr  # noqa: E402
import trace_scopes as ts  # noqa: E402
from tools import round_trace  # noqa: E402

DATA = BENCH / "tests" / "data"
TINY = DATA / "tiny_v5e.xplane.pb"
ROUND = DATA / "round_v5e.xplane.pb"
ROUND_ROUNDS, ROUND_T = 2, 2

ROUND_SCOPES = ("local_steps", "unpack", "fwd_bwd", "grad_pack", "opt_update",
                "final_eval", "exchange", "round_metrics")
# a round that carries its leaves crosses once a round, outside its steps
LEAF_ROUND_SCOPES = ("state_unpack", "local_steps", "fwd_bwd", "opt_update",
                     "final_eval", "state_pack", "exchange", "round_metrics")
READERS = ("fwd_bwd_ms.train", "pack_ms.train", "opt_update_ms.train",
           "final_eval_ms.train", "exchange_ms.train",
           "round_metrics_ms.train")
PER_STEP = ("fwd_bwd_ms.train", "pack_ms.train", "opt_update_ms.train")


# ---------------------------------------------------------------------------
# the program: scopes in the compiled round
# ---------------------------------------------------------------------------


def _tiny_round_hlo(impl: str, T: int) -> str:
    cfg = ArchConfig(name="tiny", source="test", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                     d_ff=128, vocab_size=256, mlp_type="swiglu",
                     tie_embeddings=True, dtype="bfloat16",
                     param_dtype="float32")
    model = build_model(cfg, schedule="rect")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layout = packing.layout_of(params)
    G = 2
    opt = optim.get("momentum", 0.05, packed=True, beta=0.9, impl=impl)
    exch = comm.get_exchange("server", "fp32", G)
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=T, metrics="final")
    rnd = lsgd.make_local_round(model.loss, opt, lcfg, layout=layout,
                                exchange=exch)
    state = jax.eval_shape(lambda p: lsgd.init_state(
        p, opt, n_groups=G, layout=layout, exchange=exch), params)
    batch = {"tokens": jax.ShapeDtypeStruct((G, 2, 16), jnp.int32)}
    return jax.jit(rnd).lower(state, batch).compile().as_text()


def _scope_paths(hlo: str) -> set:
    return {ts.scope_path(n) for n in re.findall(r'op_name="([^"]*)"', hlo)}


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_packed_round_hlo_carries_every_scope(impl):
    # momentum has two streams: T=1 keeps the flat buffers, T=2 the leaves
    paths = _scope_paths(_tiny_round_hlo(impl, 1))
    for scope in ROUND_SCOPES:
        assert any(scope in p.split("/") for p in paths), scope
    # the per-step scopes sit inside the local steps
    for scope in ("unpack", "fwd_bwd", "grad_pack", "opt_update"):
        assert any(ts.holds(p, ("local_steps", scope)) for p in paths)
    if impl == "pallas":
        # the fused kernel runs under its own name, inside the update
        assert any(ts.holds(p, ("local_steps", "opt_update",
                                 "fused_momentum")) for p in paths)
    paths = _scope_paths(_tiny_round_hlo(impl, 2))
    for scope in LEAF_ROUND_SCOPES:
        assert any(scope in p.split("/") for p in paths), scope
    for scope in ("fwd_bwd", "opt_update"):
        assert any(ts.holds(p, ("local_steps", scope)) for p in paths)
    for scope in ("unpack", "grad_pack", "state_unpack", "state_pack"):
        assert not any(ts.holds(p, ("local_steps", scope)) for p in paths)


# ---------------------------------------------------------------------------
# the reduction: tf_op from the event metadata, scope paths, attribution
# ---------------------------------------------------------------------------


def test_tf_ops_of_recorded_trace():
    ops = ts.read_tf_ops(str(TINY))
    assert list(ops) == ["/device:TPU:0"]
    (text, tf_op), = ops["/device:TPU:0"].items()
    assert text.startswith("%fusion = f32[1024,1024]")
    assert tf_op.split(":")[0] == "jit(<lambda>)/dot_general"
    # the scopes split the reduction's leaf time and leave its keys alone
    r = tr.reduce(tr.load_events(str(TINY)))
    assert set(r) == {"window_s", "n_devices", "busy_s", "ops", "texts",
                      "collective_s", "idle_by_annotation", "longest_gaps"}
    scopes = ts.scopes_of(r, ops)
    assert scopes["jit(<lambda>)"] == pytest.approx(3.9833e-05, rel=1e-6)
    assert sum(scopes.values()) == pytest.approx(sum(r["texts"].values()))
    assert scopes[""] == pytest.approx(r["busy_s"] - 3.9833e-05, rel=1e-6)


@pytest.mark.parametrize("tf_op,path", [
    ("jit(<lambda>)/dot_general:", "jit(<lambda>)"),
    ("add", ""),
    ("jit(round_)/final_eval/vmap(transpose(jvp()))/while/body/add",
     "jit(round_)/final_eval"),
    ("jit(round_)/local_steps/while/body/closed_call/vmap(fwd_bwd)/"
     "transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/bsd,df->bsf/dot_general:",
     "jit(round_)/local_steps/fwd_bwd/bsd,df->bsf"),
    ("jit(round_)/local_steps/while/body/closed_call/vmap(grad_pack)/"
     "concatenate", "jit(round_)/local_steps/grad_pack"),
    ("jit(round_)/local_steps/while/body/closed_call/opt_update/"
     "fused_momentum/pallas_call:", "jit(round_)/local_steps/opt_update/"
     "fused_momentum"),
    ("jit(round_)/round_metrics/jit(_where)/select_n",
     "jit(round_)/round_metrics/jit(_where)"),
])
def test_scope_path_rule(tf_op, path):
    assert ts.scope_path(tf_op) == path


def _ev(name, s, e):
    return (f"{name} ({name.split('.')[0]})", float(s), float(e),
            f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8] %x)")


def test_scopes_of_hand_made_events():
    # two chips; a loop op spanning its body; one op wrapped by
    # transforms, one in a nested scope, one unscoped, one with no tf_op
    step = "jit(r)/local_steps/while/body/closed_call"
    dev = [_ev("while.1", 0, 100), _ev("fusion.1", 0, 40),
           _ev("fusion.2", 40, 70), _ev("copy.1", 70, 100),
           _ev("fusion.3", 120, 150), _ev("add.1", 150, 160)]
    events = {"device": {"/device:TPU:0": dev, "/device:TPU:1": dev[:4]},
              "host": [("window", 0, 200)]}
    tf_ops = {"/device:TPU:0": {
        dev[1][3]: f"{step}/vmap(fwd_bwd)/transpose(jvp())/mul:",
        dev[2][3]: f"{step}/opt_update/fused_momentum/pallas_call:",
        dev[4][3]: "jit(r)/exchange/mix/vmap()/reduce_sum:",
        dev[5][3]: "add:"}}
    r = tr.reduce(events)
    scopes = ts.scopes_of(r, tf_ops)
    ns = 1e-9
    assert scopes == pytest.approx({
        "jit(r)/local_steps/fwd_bwd": 40 * ns,
        "jit(r)/local_steps/opt_update/fused_momentum": 30 * ns,
        "jit(r)/exchange/mix": 30 / 2 * ns,
        # the copy has no tf_op; the bare add has no scope
        "": (30 + 10 / 2) * ns})
    # the loop is not a leaf: every leaf second lands in one path
    assert sum(scopes.values()) == pytest.approx(sum(r["texts"].values()))
    red = dict(r, scopes=scopes)
    assert ts.scope_seconds(red, "local_steps", "fwd_bwd") == \
        pytest.approx(40 * ns)
    assert ts.scope_seconds(red, "local_steps") == pytest.approx(70 * ns)
    assert ts.scope_seconds(red, "fused_momentum") == pytest.approx(30 * ns)
    # segments must come in the path's order
    assert ts.scope_seconds(red, "fwd_bwd", "local_steps") == 0.0
    assert ts.scope_seconds(red, "final_eval") == 0.0


def test_shrink_keeps_what_the_readers_read(tmp_path):
    small = tmp_path / "small.xplane.pb"
    round_trace.shrink(str(TINY), str(small))
    assert small.stat().st_size < TINY.stat().st_size
    assert tr.reduce(tr.load_events(str(small))) == \
        tr.reduce(tr.load_events(str(TINY)))
    assert ts.read_tf_ops(str(small)) == ts.read_tf_ops(str(TINY))


# ---------------------------------------------------------------------------
# the readers, on a recorded round
# ---------------------------------------------------------------------------


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def round_ctx():
    r = tr.reduce(tr.load_events(str(ROUND)))
    r["scopes"] = ts.scopes_of(r, ts.read_tf_ops(str(ROUND)))
    return {"kind": "train", "rounds": ROUND_ROUNDS,
            "traffic": {"local_steps": ROUND_T}, "reduced": r}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_recorded_round(name, round_ctx):
    v = _reader(name)(round_ctx)
    assert isinstance(v, float) and v > 0.0
    # per step or per round: the scope's seconds over the count
    per = ROUND_ROUNDS * (ROUND_T if name in PER_STEP else 1)
    assert v * per / 1000.0 <= round_ctx["reduced"]["busy_s"]


def test_readers_and_the_rest_account_for_the_round(round_ctx):
    # the six readings, the rest of the scoped time and the unscoped time
    # add up to the leaf time of the window, which is within its busy time
    r = round_ctx["reduced"]
    read = sum(_reader(n)(round_ctx) / 1000.0 * ROUND_ROUNDS
               * (ROUND_T if n in PER_STEP else 1) for n in READERS)
    read_scopes = [("local_steps", "fwd_bwd"), ("local_steps", "unpack"),
                   ("local_steps", "grad_pack"), ("local_steps", "opt_update"),
                   ("final_eval",), ("exchange",), ("round_metrics",)]
    assert read == pytest.approx(sum(ts.scope_seconds(r, *s)
                                     for s in read_scopes))
    rest = sum(v for k, v in r["scopes"].items()
               if not any(ts.holds(k, s) for s in read_scopes))
    assert read + rest == pytest.approx(sum(r["texts"].values()))
    assert sum(r["texts"].values()) <= r["busy_s"] * (1 + 1e-9)
    # the model's forward and backward is the largest scope of a step
    assert _reader("fwd_bwd_ms.train")(round_ctx) == max(
        _reader(n)(round_ctx) for n in PER_STEP)


@pytest.mark.parametrize("name", READERS)
def test_reader_leaves_out_what_it_cannot_read(name, round_ctx):
    read = _reader(name)
    assert read(dict(round_ctx, kind="serve")) is None
    unscoped = dict(round_ctx["reduced"], scopes={"": 1.0})
    assert read(dict(round_ctx, reduced=unscoped)) is None
