"""Shared helpers for the paper-figure benchmarks."""
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro import obs  # noqa: E402
from repro.core.reference import rounds_to, run_alg1  # noqa: F401,E402

OUT_DIR = Path(__file__).resolve().parents[1] / "experiments" / "bench"


def child_env(force_devices: int = 0) -> dict:
    """Environment for a benchmark/test child process: inherit everything
    (venv interpreters, PATH, XLA flags — PR 2 broke comm_reduction by
    rebuilding a bare env), PREPEND repo src to PYTHONPATH, and
    optionally force a host-platform device count (jax locks the count
    at first init, so multi-device runs need a fresh process). A forced
    count is a CPU device count, so that child is pinned to the CPU:
    on an accelerator host the parent already holds the chip."""
    env = dict(os.environ)
    root = Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if force_devices:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={force_devices} "
            + env.get("XLA_FLAGS", "")).strip()
    return env


def save_result(name: str, payload: dict) -> Path:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    # stamp the telemetry schema so BENCH_*.json artifacts and --trace
    # files declare the same contract version (DESIGN.md §13)
    payload.setdefault("obs_schema", obs.SCHEMA_VERSION)
    p = OUT_DIR / f"{name}.json"
    p.write_text(json.dumps(payload, indent=1, default=float))
    return p


def bench_trace(name: str, meta: dict = None) -> obs.Trace:
    """A structured JSONL sink next to the bench artifact
    (experiments/bench/<name>.trace.jsonl), sharing the --trace schema."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    return obs.Trace(str(OUT_DIR / f"{name}.trace.jsonl"),
                     meta={"bench": name, **(meta or {})})


class Timer(obs.PhaseTimer):
    """Fenced wall-clock timer (DESIGN.md §13): ``t.fence(x)`` registers
    jax values the timed region produced, ``__exit__`` blocks until they
    are ready before reading the clock. Back-compat with the old naive
    timer — ``with Timer() as t: ...`` then ``t.seconds``."""
