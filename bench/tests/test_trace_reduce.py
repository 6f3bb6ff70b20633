"""The trace reduction, on a small trace recorded on one TPU v5e (three
steps of a jitted 1024 x 1024 matmul + tanh, each followed by a 2 ms
host sleep annotated ``host_wait``, all inside a ``window`` annotation)
and on hand-made events."""
from pathlib import Path

import pytest

import trace_reduce as tr

TINY = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def tiny():
    return tr.load_events(str(TINY))


def test_recorded_trace_window_busy_idle(tiny):
    r = tr.reduce(tiny)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.009669059, rel=1e-6)
    # busy is the union of the op intervals inside the window
    lo, hi = tr.window_of(tiny)
    evs = next(iter(tiny["device"].values()))
    merged = tr.union(tr.clip([(s, e) for _, s, e, _ in evs], lo, hi))
    assert r["busy_s"] == pytest.approx(sum(e - s for s, e in merged) * 1e-9)
    assert r["busy_s"] == pytest.approx(5.1805e-05, rel=1e-6)
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_by_annotation"].values()) == pytest.approx(idle)
    # the device sat idle mostly while the host slept, the rest while it
    # dispatched and waited on each step
    by = r["idle_by_annotation"]
    assert by["host_wait"] == pytest.approx(0.00728286, rel=1e-5)
    assert by["host_wait"] > 0.7 * idle
    assert by["step"] + by["PjitFunction(<lambda>)"] > 0.2 * idle


def test_recorded_trace_kernel_time_by_name(tiny):
    r = tr.reduce(tiny)
    assert r["ops"]["fusion (fusion)"] == pytest.approx(3.9833e-05, rel=1e-6)
    assert tr.match_seconds(r, [r"^%fusion = f32\[1024,1024\]"]) == \
        pytest.approx(3.9833e-05, rel=1e-6)
    assert tr.match_seconds(r, [r"tpu_custom_call"]) == 0.0
    assert r["collective_s"] == 0.0
    b = tr.breakdown(r)
    assert b["device_ops"][0][0] == "fusion (fusion)"
    assert b["idle_gaps"][0][0] == "host_wait"


def _ev(name, s, e):
    return (f"{name} ({name.split('.')[0]})", float(s), float(e),
            f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8] %x)")


def test_union_nesting_and_collectives_on_two_devices():
    # device 0: a loop op spanning two body ops, one overlap; device 1: an
    # all-reduce and a fusion
    dev0 = [_ev("while.1", 0, 100), _ev("fusion.1", 10, 40),
            _ev("fusion.2", 30, 60), _ev("fusion.3", 150, 170)]
    dev1 = [_ev("all-reduce.1", 0, 50), _ev("fusion.9", 60, 80)]
    events = {"device": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
              "async": {"/device:TPU:1": [_ev("all-gather.2", 90, 110)]},
              "host": [("window", 0, 200), ("round", 0, 120),
                       ("data", 120, 200)]}
    r = tr.reduce(events)
    assert r["window_s"] == pytest.approx(200e-9)
    # busy: dev0 union [0,100] + [150,170] = 120; dev1 50 + 20 = 70
    assert r["busy_s"] == pytest.approx((120 + 70) / 2 * 1e-9)
    # the loop is not a leaf: its time is its body's
    assert "while.1 (while)" not in r["ops"]
    assert r["ops"]["fusion.1 (fusion)"] == pytest.approx(30 / 2 * 1e-9)
    # collectives: 50 on the ops line + 20 async, averaged over 2 chips
    assert r["collective_s"] == pytest.approx(70 / 2 * 1e-9)
    # device 0's idle gaps: (100,150) is 20 in 'round' and 30 in 'data';
    # (170,200) in 'data'
    assert r["idle_by_annotation"] == pytest.approx(
        {"round": 20e-9, "data": 60e-9})
    assert r["longest_gaps"][0] == ("data", pytest.approx(50e-9))


def test_window_clips_ops():
    dev = [_ev("fusion.1", 0, 100)]
    events = {"device": {"/device:TPU:0": dev},
              "host": [("window", 50, 150)]}
    r = tr.reduce(events)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["idle_by_annotation"] == pytest.approx(
        {"(no annotation)": 50e-9})


def test_short_name():
    text = ("%closed_call.40 = (f32[4,8]{1,0:T(4,128)}, f32[4,8]{1,0}) "
            "custom-call(f32[4,8]{1,0} %a), custom_call_target=\"x\"")
    assert tr.short_name(text) == "closed_call.40 (custom-call)"
