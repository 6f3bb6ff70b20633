"""Serving launcher: the continuous-batching engine (repro.serve) over a
request workload, with checkpoint->serve handoff.

On this CPU container run reduced configs:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --reduced \
      --requests 8 --rate 4 --gen 8
Restore trained weights from a ``launch/train.py --checkpoint`` file
(pytree or packed flat-buffer format):
  ... --from-checkpoint experiments/ckpt/qwen3
All timings are phase-fenced (obs.Trace): prefill / decode_step phases
block_until_ready before reading the clock, and ``--trace`` writes the
per-step JSONL that ``python -m repro.obs.report <file> --check``
validates. ``--check-parity`` replays every request alone through an
engine of the same geometry (the same compiled programs, every other
slot idle) and asserts identical tokens (the CI serve smoke). The same
slot count matters on a TPU: a batch of 1 compiles to a different
program than a batch of 4, which rounds differently (on a v5e, by about
one bf16 step of a logit) and can flip a near-tied greedy argmax without
any leak between slots.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch import compile_cache
from repro.models import build_model
from repro.obs.trace import Trace
from repro.serve import (Engine, EngineConfig, Request, drive_workload,
                         poisson_workload, restore_params)


def build_engine(model, params, args, policy: str,
                 trace=None) -> Engine:
    return Engine(model, params, EngineConfig(
        n_slots=args.slots, page_size=args.page_size,
        max_prompt=args.prompt_max, max_new=args.gen_max,
        impl=args.impl, policy=policy), trace=trace)


def main(argv=None) -> dict:
    """Serve a workload for ``argv`` (default: the command line). Returns
    the run's record: requests done, tokens committed, and whether the
    isolated-replay parity check ran and held (``parity_ok``: None when
    not asked for; a failure raises SystemExit)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=("continuous", "static"))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate (req/s, virtual clock)")
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8,
                    help="min generated tokens per request")
    ap.add_argument("--gen-max", type=int, default=16)
    ap.add_argument("--impl", default="auto",
                    choices=("auto", "jnp", "pallas"),
                    help="decode-attention impl")
    ap.add_argument("--from-checkpoint", default="",
                    help="restore params saved by launch/train.py "
                         "(pytree or packed)")
    ap.add_argument("--trace", default="", help="JSONL trace sink")
    ap.add_argument("--check-parity", action="store_true",
                    help="replay each request alone through an engine of "
                         "the same geometry; assert identical tokens")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    if args.from_checkpoint:
        params = restore_params(args.from_checkpoint, model)
        print(f"params <- {args.from_checkpoint}.npz")
    else:
        params = model.init(jax.random.PRNGKey(args.seed))

    trace = Trace(args.trace or None,
                  meta={"launcher": "serve", "arch": cfg.name,
                        "engine": args.engine, "slots": args.slots,
                        "page_size": args.page_size})
    engine = build_engine(model, params, args, args.engine, trace)
    engine.warmup()

    gen = (min(args.gen, args.gen_max), args.gen_max)
    reqs = poisson_workload(args.rate, args.requests, seed=args.seed,
                            prompt_len=(args.prompt_min, args.prompt_max),
                            max_new=gen, vocab=cfg.vocab_size)
    done, makespan = drive_workload(
        engine, [Request(r.rid, r.prompt.copy(), r.max_new, r.arrival)
                 for r in reqs])
    trace.close()

    lat = np.sort([c.latency for c in done])
    committed = sum(len(c.tokens) for c in done)
    print(f"arch={cfg.name} engine={args.engine} slots={args.slots} "
          f"page={args.page_size} impl={args.impl}")
    print(f"{len(done)} requests, {committed} tokens committed in "
          f"{makespan:.2f}s virtual ({committed / max(makespan, 1e-9):.1f}"
          " tok/s)")
    print(f"latency p50 {np.percentile(lat, 50):.3f}s "
          f"p99 {np.percentile(lat, 99):.3f}s")
    if args.trace:
        print(f"trace -> {args.trace} ({trace.n_records} records)")
    record = {"requests": len(done), "committed": committed,
              "parity_ok": None}

    if args.check_parity:
        iso = build_engine(model, params, args, args.engine)
        got = {c.rid: c.tokens for c in done}
        bad = 0
        for r in reqs:
            ref = iso.run([Request(r.rid, r.prompt.copy(), r.max_new)])
            if got[r.rid] != ref[0].tokens:
                bad += 1
                print(f"PARITY FAIL rid={r.rid}: engine {got[r.rid]} "
                      f"!= isolated {ref[0].tokens}")
        if bad:
            raise SystemExit(f"parity check failed for {bad} request(s)")
        print(f"parity OK: {len(reqs)} requests identical to isolated "
              "decode")
        record["parity_ok"] = True
    return record


if __name__ == "__main__":
    main()
