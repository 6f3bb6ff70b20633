"""Shared pieces of the benchmark harness: paths, seeds, configurations,
weights, device facts and host annotations.

Nothing here imports JAX at module level: ``run.py`` fixes the compile
cache and platform checks before JAX is loaded.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
# JAX's persistent compilation cache: one fixed directory inside the
# checkout, so that only the first run of a cell there compiles.
CACHE_DIR = ROOT / ".jax_cache"
# what a run writes (traces, records): inside the checkout, ignored by git
OUT_DIR = ROOT / "bench_out"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_of(name: str, s: dict | None = None) -> dict:
    """The workload entry of ``BENCHMARK.json`` (or of the spec ``s``),
    with its configuration entry, configuration file and traffic file
    resolved."""
    s = s or spec()
    cells = {w["name"]: w for w in s["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = dict(cells[name])
    conf = {c["name"]: c for c in s["configs"]}[w["config"]]
    w["config_entry"] = conf
    w["config_file"] = load_json(ROOT / conf["file"])
    w["traffic_file"] = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = BENCH / "limits" / f"{name}.json"
    w["limits"] = load_json(limits)["limits"] if limits.exists() else {}
    w["end_to_end"] = [m for m in s["end_to_end"]
                       if name in m.get("workloads", [name])]
    w["per_layer"] = [m for m in s["per_layer"]
                      if name in m.get("workloads", [name])]
    return w


def src_on_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def arch_config(config_file: dict):
    """The program's ``ArchConfig`` built from a configuration file's
    ``arch`` block (the sizes as run)."""
    src_on_path()
    from repro.configs.base import ArchConfig
    return ArchConfig(**config_file["arch"])


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------


def base_key(seed: int):
    """A PRNG key from any whole-number seed (also past 32 bits: the high
    word is folded in, as ``PRNGKey`` alone would drop it)."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise SystemExit(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def np_rng(seed: int, lane: int):
    import numpy as np
    return np.random.default_rng([int(seed), int(lane)])


# lanes keep the streams drawn from one seed apart
LANE_WEIGHTS, LANE_DATA, LANE_TRAFFIC, LANE_SAMPLE = 1, 2, 3, 4


# ---------------------------------------------------------------------------
# weights: made on the device in one jitted call, from the seed alone
# ---------------------------------------------------------------------------


def make_weights(specs: dict, seed: int):
    """Weights for a flat ``{path: (shape, kind)}`` table: ``kind`` is
    ``"ones"`` (norm scales) or ``"normal"`` (N(0, 0.02^2)), float32.
    Leaf ``i`` of the sorted paths draws from ``fold_in(key, i)``, so the
    program's copy and the reference's copy agree whatever nests them.
    Returns a flat ``{path: array}`` dict."""
    import jax
    import jax.numpy as jnp

    paths = sorted(specs)

    def gen(key):
        out = {}
        for i, p in enumerate(paths):
            shape, kind = specs[p]
            if kind == "ones":
                out[p] = jnp.ones(shape, jnp.float32)
            else:
                k = jax.random.fold_in(key, i)
                out[p] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        return out

    key = jax.random.fold_in(base_key(seed), LANE_WEIGHTS)
    return jax.jit(gen)(key)


def nest(flat: dict) -> dict:
    """``{"a/b/c": x}`` -> ``{"a": {"b": {"c": x}}}``."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


# ---------------------------------------------------------------------------
# device facts
# ---------------------------------------------------------------------------


def devices_for(chips: int):
    """The first ``chips`` accelerator devices; exits nonzero without a
    TPU or with fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX reports {devs[0].platform!r}); a device "
              "metric is never taken on another platform", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


def start_trace(trace_dir) -> None:
    """The profiler over the window, without its Python-frame tracer: the
    host line then holds only annotations, and a long window does not
    overflow it."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's trace (names idle gaps)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class CompileCounter:
    """Counts compilations (and persistent-cache loads) while armed: the
    measured window must contain none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1


def env_setup() -> None:
    """Before JAX loads: the compile cache at its fixed path, and every
    compiled program kept there."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # no floor on an entry's size and no cap on the cache's (a cap set in
    # the environment would leave the large round programs out)
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
