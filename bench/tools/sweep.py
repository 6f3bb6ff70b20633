#!/usr/bin/env python3
"""Find a serving cell's knee once, by a sweep of fixed open-loop rates
on one engine (not run by the benchmark's own runs).

    python bench/tools/sweep.py --workload <cell> --rates 4,8,12 \
        [--seconds 20] [--seed 1]

For each rate: requests sent, tokens per second completed, TTFT and
inter-token p95, and the queue left at the window's close (a backlog
that grows through the window means the rate is past the knee). One JSON
line per rate.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import common  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    common.env_setup()
    cell = common.cell_of(args.workload)
    common.devices_for(cell["chips"])
    common.src_on_path()
    from drivers import serve as drv
    from repro.serve import Request
    tf = dict(cell["traffic_file"])
    V = cell["config_file"]["arch"]["vocab_size"]
    for rate in [float(r) for r in args.rates.split(",")]:
        engine = drv.build_engine(cell, args.seed)
        engine.warmup()
        tf["rate_per_s"] = rate
        reqs = drv.schedule(tf, args.seed, args.seconds, V)
        w = drv.serve_window(engine, reqs, args.seconds, Request)
        m = drv.latency_metrics(w)
        queued, active = len(engine.queue), engine.n_active()
        half = [len(ts) for r, ts in w["times"].items()]
        print(json.dumps(dict(rate=rate, sent=len(w["sent"]),
                              done=len(w["done"]), queued_at_close=queued,
                              active_at_close=active,
                              tokens=sum(half), **m)), flush=True)
        engine.pool = None
        del engine
        gc.collect()


if __name__ == "__main__":
    main()
