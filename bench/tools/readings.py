#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs): the program's numbers on many seeds, and the
control's and the planted faults' on a few, at the cell's own size, all
in one process.

    python bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--seconds 15]

Training: the program's first rounds against the reference; the control
is the reference computed one precision below the configuration's
(its file's ``control``) put in the program's place;
the faults are planted in the reference put in the program's place (half
of each group's rows; no exchange). Serving: a short window at the
cell's load per seed, then the served tokens' widest logit gap, and the
control's (the token the lower-precision reference puts first, in the
float32 reference). One JSON line per reading goes to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import common  # noqa: E402


def control_mode(cell) -> str:
    """The precision one step below the one the configuration states
    (its file's ``control``), as ``refs/decoder.py`` names it."""
    return cell["config_file"]["control"]


def emit(**rec):
    print(json.dumps(rec), flush=True)


def train_readings(cell, seeds, control_seeds, devs):
    from drivers import train as drv
    n = cell["traffic_file"]["check_rounds"]
    for seed in seeds:
        t0 = time.perf_counter()
        rnd = drv.Round(cell, seed, devs)
        prog = rnd.warm(n)
        gen = rnd.gen
        rnd.state = None
        del rnd
        gc.collect()
        ref = drv.reference(cell, seed, gen, n)
        emit(seed=seed, who="program", numbers=drv.compare(prog, ref),
             seconds=time.perf_counter() - t0)
        if seed in control_seeds:
            for who, mode, fault in ((control_mode(cell), control_mode(cell),
                                      ""),
                                     ("fault:half", "f32", "half"),
                                     ("fault:noexchange", "f32",
                                      "noexchange")):
                t0 = time.perf_counter()
                other = drv.reference(cell, seed, gen, n, mode=mode,
                                      fault=fault)
                emit(seed=seed, who=who, numbers=drv.compare(other, ref),
                     seconds=time.perf_counter() - t0)


def serve_readings(cell, seeds, control_seeds, seconds):
    from drivers import serve as drv
    from repro.serve import Request
    tf = cell["traffic_file"]
    arch = cell["config_file"]["arch"]
    for seed in seeds:
        t0 = time.perf_counter()
        engine = drv.build_engine(cell, seed)
        engine.warmup()
        reqs = drv.schedule(tf, seed, seconds, arch["vocab_size"])
        length = engine.bucket + tf["engine"]["max_new"]
        w = drv.serve_window(engine, reqs, seconds, Request)
        engine.pool = None
        del engine
        gc.collect()
        pick = drv.sample_requests(w, seed, tf["check"]["sample_requests"])
        items = [(reqs[r][1], w["done"][r].tokens) for r in pick]
        modes = ("f32",) + ((control_mode(cell),)
                            if seed in control_seeds else ())
        gaps = drv.reference_gaps(arch, seed, items, length, modes)
        emit(seed=seed, who="program",
             numbers={"max_logit_gap": gaps["f32"]},
             tokens=sum(len(t) for _, t in items),
             done=len(w["done"]), seconds=time.perf_counter() - t0)
        for m in modes[1:]:
            emit(seed=seed, who=m, numbers={"max_logit_gap": gaps[m]})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    common.env_setup()
    cell = common.cell_of(args.workload)
    devs = common.devices_for(cell["chips"])
    common.src_on_path()
    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = {int(s) for s in args.control_seeds.split(",") if s}
    if cell["traffic_file"]["kind"] == "train":
        train_readings(cell, seeds, cseeds, devs)
    else:
        serve_readings(cell, seeds, cseeds, args.seconds)


if __name__ == "__main__":
    main()
