#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the traffic's ``kind`` picks the
driver (``bench/drivers/<kind>.py``), which builds the program from the
configuration, warms up every shape, measures for ``--seconds`` and
checks what the timed path produced against the plain reference. With
``--trace 1`` the window runs under the profiler and each per-layer
metric is read by its own reader, ``bench/metrics/<metric>.py``, from the
reduced trace and the run's context.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, and, last,
``checks``: each number compared with its limit. The same numbers are
the last lines on standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import common  # noqa: E402


def read_metric(name: str, ctx: dict):
    """The reader ``bench/metrics/<name>.py`` applied to the context;
    None when it finds nothing to read."""
    path = common.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def judge(checks: dict, limits: dict):
    """``correct`` is true when every number compared is finite and at
    most its limit; a number without a limit fails."""
    table, ok = {}, True
    for k, v in checks.items():
        lim = limits.get(k)
        table[k] = {"value": v, "limit": lim}
        if lim is None or v is None or not math.isfinite(v) or v > lim:
            ok = False
    return ok, table


def run_cell(name: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of the cell ``name`` on the chips it asks for; returns the
    result record."""
    common.env_setup()
    cell = common.cell_of(name)
    return measure(cell, seed, seconds, trace,
                   common.devices_for(cell["chips"]))


def measure(cell: dict, seed: int, seconds: float, trace: bool,
            devs) -> dict:
    """Everything of a run after the look for chips: the driver's set-up,
    window and check on ``devs``, the metrics and the verdict."""
    name = cell["name"]
    counter = common.CompileCounter()
    driver = importlib.import_module(
        f"drivers.{cell['traffic_file']['kind']}")
    trace_dir = None
    if trace:
        trace_dir = common.OUT_DIR / "trace" / name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    out = driver.run(cell, seed, seconds, trace_dir, devs, counter)
    setup_s = out["t_window"] - T_START
    device = out["device"]
    metrics = {}
    if trace:
        import trace_reduce as tr
        reduced = tr.reduce(tr.load_events(str(trace_dir)))
        ctx = dict(out["ctx"], reduced=reduced,
                   peaks=common.peaks_for(device["kind"]))
        for m in cell["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = dict(device, busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        breakdown = tr.breakdown(reduced)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(out["e2e"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values.pop(m["name"]),
                                  "unit": m["unit"]}
    correct, table = judge(out["checks"], cell["limits"])
    rec = {"correct": correct and out["failed"] == 0,
           "attempted": out["attempted"], "failed": out["failed"],
           "metrics": metrics, "device": device}
    if trace:
        rec["breakdown"] = breakdown
    else:
        # the driver's other end-to-end readings, not the cell's metrics
        rec["observed"] = values
    rec["compiles_in_window"] = counter.count
    rec["checks"] = table
    rec["_readings"] = out.get("readings")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rec = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    readings = rec.pop("_readings")
    common.OUT_DIR.mkdir(exist_ok=True)
    with open(common.OUT_DIR / "readings.jsonl", "a") as f:
        f.write(json.dumps({"workload": args.workload, "seed": args.seed,
                            "trace": args.trace, "readings": readings,
                            "checks": rec["checks"]}) + "\n")
    print(f"compiles in the window: {rec.pop('compiles_in_window')}",
          flush=True)
    ref_s = ((readings or {}).get("reference") or {}).get("seconds")
    if ref_s is not None:
        print(f"reference: {ref_s:.1f} s after the window", flush=True)
    for k, v in rec["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
