#!/usr/bin/env python3
"""Bring-up smoke run of the trainer and the serve engine on a TPU.

    python chip_smoke.py             # one chip: trainer phase, serve phase
    python chip_smoke.py --chips 4   # four chips: one group per chip

Both drive the normal entry points (``repro.launch.train.main`` and
``repro.launch.serve.main``, called with argv) at the full width of
paper-lenet (8 layers, d_model 768, 12 heads, vocab 32,000, 124.7M
params) with random weights from a seed. Nothing here claims a speed.

One chip:
  trainer  3 rounds of packed momentum local SGD (G=4, T=4, per-group
           batch 4 x 256 tokens, server/fp32) that must give finite,
           falling losses and a compiled round holding Pallas TPU kernels;
           one int8-codec round; one round with --impl pallas and one with
           --impl jnp from the same seed (T=1, so the round keeps the flat
           buffers and the fused update kernel runs), whose params must
           agree within PARITY_TOL.
  serve    the trainer's checkpoint through the continuous-batching engine
           (Pallas decode attention), replaying every request in isolation
           (--check-parity).
Four chips: 2 server/fp32 rounds and 1 ring round with the groups spread
one per chip (each device holding a (1, Np) shard, a collective in the
compiled round), each against the same rounds on one device, both at
full f32 matmul precision, params within PARITY_TOL.

Each phase runs in a child process; this parent never imports JAX, so the
chip is free for the child. A phase writes its record to
``.chip_smoke/<phase>.json``. The last line of standard output is
``{"ok": true, "device": {...}}`` when every phase passed; without a TPU,
or without the rest of the repository next to this file, the script
exits nonzero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
WORK = ROOT / ".chip_smoke"
BUDGET_S = 1150          # the whole run, compilation included

# Largest |param difference| allowed between two runs of the same rounds
# from the same seed: Pallas vs jnp update kernels, and groups spread over
# chips vs all on one device. Both differ only in rounding (FMA
# contraction, reduction order, the all-reduce). Seen so far: 0.0 for
# Pallas vs jnp on a v5e, 2.4e-7 for a ring round spread over 4 CPU
# devices at reduced size. One round of 4 momentum steps moves params by
# ~1e-4..1e-3 at lr 0.05, so a mixing or kernel bug shows far above this.
PARITY_TOL = 1e-6

TRAIN = ["--arch", "paper-lenet", "--packed", "--opt", "momentum",
         "--groups", "4", "--t-inner", "4", "--per-group", "4",
         "--seq", "256", "--lr", "0.05", "--seed", "0"]
CKPT = WORK / "paper-lenet"


# ---------------------------------------------------------------------------
# phases (each runs in its own child process)
# ---------------------------------------------------------------------------


def _jax():
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    return jax


def _host_params(run):
    """Drop a train record's device state; keep its params on the host."""
    import jax
    import numpy as np
    run.pop("state")
    return jax.tree.map(np.asarray, run.pop("params"))


def _max_abs_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _finite(history) -> bool:
    return all(math.isfinite(h["loss"]) and math.isfinite(h["grad_sq"])
               for h in history)


def phase_probe() -> dict:
    jax = _jax()
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def phase_train() -> dict:
    jax = _jax()
    from repro.launch import train

    out = {}
    print("== trainer: 3 rounds, server/fp32, --impl auto", flush=True)
    run = train.main(TRAIN + ["--rounds", "3", "--codec", "fp32",
                              "--checkpoint", str(CKPT)])
    hist = run["history"]
    kernels = train.custom_calls(run["hlo"])
    _host_params(run)
    for n, h in enumerate(hist):
        print(f"round {n}: loss {h['loss']!r} gsq {h['grad_sq']!r}")
    print(f"compile_s {run['compile_s']!r}; tpu_custom_call ops in the "
          f"compiled round: {kernels} (a count)")
    _check(_finite(hist), "non-finite loss or grad_sq")
    _check(hist[-1]["loss"] < hist[0]["loss"],
           f"loss did not fall: {hist[0]['loss']} -> {hist[-1]['loss']}")
    _check(kernels > 0, "no Pallas kernel in the compiled round")
    out.update(history=hist, compile_s=run["compile_s"], kernels=kernels)

    print("== trainer: 1 round, server/int8", flush=True)
    run = train.main(TRAIN + ["--rounds", "1", "--codec", "int8"])
    _host_params(run)
    print(f"int8 round: loss {run['history'][0]['loss']!r} compile_s "
          f"{run['compile_s']!r} tpu_custom_call ops "
          f"{train.custom_calls(run['hlo'])} (a count)")
    _check(_finite(run["history"]), "non-finite int8 round")
    out.update(int8=run["history"][0], int8_compile_s=run["compile_s"])

    print("== trainer: 1 round --impl pallas vs --impl jnp", flush=True)
    params = {}
    for impl in ("pallas", "jnp"):
        # T=1 < 2 streams: the round keeps the flat buffers, where the
        # fused update kernel runs (DESIGN.md §6)
        run = train.main(TRAIN + ["--rounds", "1", "--codec", "fp32",
                                  "--impl", impl, "--t-inner", "1"])
        params[impl] = _host_params(run)
    diff = _max_abs_diff(params["pallas"], params["jnp"])
    print(f"pallas vs jnp: max |param diff| {diff!r} (tolerance "
          f"{PARITY_TOL})")
    _check(diff <= PARITY_TOL, "Pallas and jnp rounds disagree")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use {peak!r}")
    out.update(impl_max_abs_diff=diff, peak_bytes_in_use=peak)
    return out


def phase_serve() -> dict:
    _jax()
    from repro.launch import serve

    print("== serve: trainer checkpoint, continuous engine", flush=True)
    rec = serve.main(["--arch", "paper-lenet", "--from-checkpoint",
                      str(CKPT), "--engine", "continuous", "--impl", "auto",
                      "--slots", "4", "--page-size", "16", "--requests",
                      "8", "--prompt-max", "64", "--gen", "8", "--gen-max",
                      "16", "--check-parity"])
    print(f"tokens committed {rec['committed']} (a count); parity "
          f"{'OK' if rec['parity_ok'] else 'FAILED'}")
    _check(rec["committed"] > 0 and rec["parity_ok"], "serve phase")
    return rec


def phase_mesh() -> dict:
    jax = _jax()
    from repro.launch import train

    devices = jax.devices()
    _check(len(devices) >= 4, f"{len(devices)} devices, need 4")
    out = {}
    for comm, rounds in (("server", 2), ("ring", 1)):
        argv = TRAIN + ["--rounds", str(rounds), "--comm", comm,
                        "--codec", "fp32"]
        # Placement must not change the math. At the TPU's default f32
        # matmul precision (one bf16 pass) the partitioned and the
        # one-device programs round differently: on four v5e chips two
        # server rounds differed by 2.9e-5 in params, and already in
        # round 0's loss and grad norm, taken before any exchange. At
        # full f32 precision only the order of sums differs, so a mixing
        # fault shows far above PARITY_TOL.
        with jax.default_matmul_precision("highest"):
            print(f"== {rounds} {comm} round(s), one group per chip",
                  flush=True)
            run = train.main(argv)
            buf = run["state"]["params"]
            shape = tuple(buf.shape)
            shards = {tuple(s.data.shape) for s in buf.addressable_shards}
            del buf
            hlo = run["hlo"]
            collectives = {op: hlo.count(f" {op}(")
                           + hlo.count(f" {op}-start(")
                           for op in ("all-reduce", "collective-permute")}
            spread = _host_params(run)
            print(f"{comm}: addressable shards of the {shape} buffer: "
                  f"{sorted(shards)}; collectives in the compiled round: "
                  f"{collectives} (counts); compile_s {run['compile_s']!r}",
                  flush=True)
            print(f"== {rounds} {comm} round(s), all groups on one device",
                  flush=True)
            one = train.main(argv, devices=devices[:1])
        diff = _max_abs_diff(spread, _host_params(one))
        print(f"{comm}: spread vs one device: max |param diff| {diff!r} "
              f"(tolerance {PARITY_TOL})", flush=True)
        out[comm] = {"history": run["history"],
                     "history_one_device": one["history"],
                     "shards": sorted(shards), "collectives": collectives,
                     "compile_s": run["compile_s"], "max_abs_diff": diff,
                     "one_group_per_chip": shards == {(1, shape[1])}}
    # every comparison is made before any is judged
    for comm, rec in out.items():
        _check(rec["one_group_per_chip"], f"{comm}: not one group per chip")
        _check(sum(rec["collectives"].values()) > 0,
               f"{comm}: no collective in the HLO")
        _check(rec["max_abs_diff"] <= PARITY_TOL,
               f"{comm}: spread and one-device rounds disagree")
    return out


PHASES = {"probe": phase_probe, "train": phase_train, "serve": phase_serve,
          "mesh": phase_mesh}


# ---------------------------------------------------------------------------
# parent: one child process per phase
# ---------------------------------------------------------------------------


def run_phase(name: str, deadline: float) -> dict:
    left = deadline - time.monotonic()
    if left <= 0:
        raise SystemExit(f"chip_smoke: no time left for phase {name}")
    result = WORK / f"{name}.json"
    result.unlink(missing_ok=True)
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), "--phase", name],
            timeout=left)
    except subprocess.TimeoutExpired:
        raise SystemExit(f"chip_smoke: phase {name} ran out of time")
    if proc.returncode != 0 or not result.exists():
        raise SystemExit(f"chip_smoke: phase {name} failed "
                         f"(exit {proc.returncode})")
    print(f"phase {name}: {time.monotonic() - t0:.1f}s wall", flush=True)
    return json.loads(result.read_text())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    WORK.mkdir(exist_ok=True)
    if args.phase:
        record = PHASES[args.phase]()
        (WORK / f"{args.phase}.json").write_text(
            json.dumps(record, indent=1, default=str))
        return 0

    deadline = time.monotonic() + BUDGET_S
    device = run_phase("probe", deadline)
    print(f"devices: {device}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX reports {device['platform']!r})",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: {device['count']} chip(s), --chips "
              f"{args.chips} needs {args.chips}", file=sys.stderr)
        return 1
    phases = ("mesh",) if args.chips == 4 else ("train", "serve")
    for name in phases:
        run_phase(name, deadline)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
