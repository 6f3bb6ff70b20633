"""Phase-fenced tracing: honest wall-clock + a structured JSONL sink.

The problem this solves (ISSUE 7): jitted calls return BEFORE the work
finishes (async dispatch), so ``t0 = time.time(); state = step(...);
dt = time.time() - t0`` measures dispatch, not compute. Every phase
timer here fences with ``jax.block_until_ready`` on the values the
phase produced before reading the clock, and wraps the phase in
``jax.profiler.TraceAnnotation`` so a perfetto dump (``--profile``)
shows the same phase boundaries the JSONL records.

Sink format (one JSON object per line):

  {"kind": "meta", "schema": 1, ...caller meta...}        # first line
  {"kind": "round", "round": n, "phase_s": {...}, "metrics": {...}}
  {"kind": "step"|"bench"|"dryrun", ...}                  # other events

``Trace(path=None)`` is a null sink that still fences and times — the
launchers use one unconditionally so printed timings are honest even
when nothing is written.
"""
from __future__ import annotations

import contextlib
import json
import time
from pathlib import Path
from typing import Any, Dict, Optional


def to_jsonable(x):
    """Round metrics -> plain JSON: device arrays become floats/lists
    (forces a host transfer — callers fence first, so this is cheap and
    never blocks on in-flight work)."""
    if isinstance(x, dict):
        return {k: to_jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [to_jsonable(v) for v in x]
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    if hasattr(x, "ndim"):                  # jax/np array
        import numpy as np
        a = np.asarray(x)
        if a.ndim == 0:
            return (int(a) if np.issubdtype(a.dtype, np.integer)
                    else float(a))
        return a.astype(float).tolist()
    return float(x)


class PhaseTimer:
    """Fenced wall-clock timer: ``fence(x)`` registers values the phase
    produced; ``__exit__`` blocks until they are ready, THEN reads the
    clock. Usable standalone (``with PhaseTimer() as t: ...; t.seconds``)
    and as the engine under ``Trace.phase``."""

    def __init__(self):
        self.seconds = 0.0
        self._fence = None

    def __enter__(self):
        self._fence = None
        self.t0 = time.perf_counter()
        return self

    def fence(self, x):
        self._fence = x
        return x

    # make the timer callable so ``with trace.phase("round") as f:
    # state, m = f(rnd(state, batch))`` reads naturally
    __call__ = fence

    def __exit__(self, *exc):
        if self._fence is not None:
            import jax
            jax.block_until_ready(self._fence)
        self.seconds = time.perf_counter() - self.t0
        return False


class Trace:
    """Structured trace sink + phase fencing (DESIGN.md §13).

    ``path=None`` disables the file sink but keeps the fencing/timing
    behavior, so launchers run one code path. The meta header is written
    lazily on the first record so callers can build the trace before
    knowing every meta field (``meta.update`` is fine until then).
    """

    def __init__(self, path: Optional[str] = None,
                 meta: Optional[Dict[str, Any]] = None):
        self.path = Path(path) if path else None
        self.meta = dict(meta or {})
        self._phases: Dict[str, float] = {}
        self._fh = None
        self.n_records = 0

    # -- phases -----------------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """Fenced, profiler-annotated phase. Durations accumulate under
        ``name`` until the next ``emit_round`` pops them — several
        phases (data, round, checkpoint) add up to one record."""
        import jax
        with jax.profiler.TraceAnnotation(name):
            with PhaseTimer() as t:
                yield t
        self._phases[name] = self._phases.get(name, 0.0) + t.seconds

    def phase_seconds(self, name: str) -> float:
        """Accumulated seconds of ``name`` since the last emit."""
        return self._phases.get(name, 0.0)

    def add_phase(self, name: str, seconds: float) -> None:
        """Record a DERIVED phase duration (e.g. the calibrated
        ``exchange_exposed``/``exchange_total`` split, DESIGN.md §14) so
        it rides the next ``emit_round`` like a fenced phase. Only for
        values computed FROM fenced measurements — raw ``time.time``
        deltas around jitted calls stay lies."""
        self._phases[name] = self._phases.get(name, 0.0) + float(seconds)

    def take_phases(self) -> Dict[str, float]:
        out, self._phases = self._phases, {}
        return out

    # -- the sink ---------------------------------------------------------

    def _write(self, rec: dict):
        self.n_records += 1
        if self.path is None:
            return
        if self._fh is None:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._fh = open(self.path, "w")
            from repro import obs
            header = {"kind": "meta", "schema": obs.SCHEMA_VERSION}
            header.update(to_jsonable(self.meta))
            self._fh.write(json.dumps(header) + "\n")
        self._fh.write(json.dumps(rec) + "\n")
        self._fh.flush()

    def emit_round(self, n: int, metrics: Optional[dict] = None,
                   kind: str = "round", **fields) -> dict:
        """One per-round record: accumulated phase durations + the
        round's metric dict (converted to JSON — callers fence first via
        ``phase``). Returns the record so launchers can print from it."""
        rec = {"kind": kind, "round": int(n),
               "phase_s": {k: round(v, 6)
                           for k, v in self.take_phases().items()},
               "metrics": to_jsonable(metrics or {})}
        rec.update(to_jsonable(fields))
        self._write(rec)
        return rec

    def emit(self, kind: str, **fields) -> dict:
        """A free-form event record (bench cells, dryrun phases)."""
        rec = {"kind": kind}
        rec.update(to_jsonable(fields))
        self._write(rec)
        return rec

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def exchange_phases(round_s: float, local_ref_s: float, exch_ref_s: float,
                    *, overlap: bool) -> Dict[str, float]:
    """The honest exchange-time split (DESIGN.md §14).

    Intra-graph fences cannot separate overlapped phases (XLA schedules
    them concurrently; on a serial CPU backend dispatch order would be
    reported as if it were concurrency). Instead the launcher calibrates
    two references ONCE — ``local_ref_s``: the same round built with
    comm='none' (pure local compute), ``exch_ref_s``: the exchange ops
    jitted standalone — and derives per round:

      exchange_exposed = max(0, round_s - local_ref_s)
          the exchange time actually ON the critical path this round;
      exchange_total   = the standalone exchange cost (overlap mode,
          floored at exposed so noise never reports >100% hiding), or
          == exposed for a barrier round (nothing is hidden by
          construction).

    Overlap efficiency = 1 - exposed/total. On a single-core host the
    backend executes serially, exposed ≈ total, and the efficiency is
    honestly ≈ 0 — the hiding is real only where the backend can run
    collectives concurrently with compute."""
    exposed = max(0.0, float(round_s) - float(local_ref_s))
    total = max(float(exch_ref_s), exposed) if overlap else exposed
    return {"exchange_exposed": exposed, "exchange_total": total}


@contextlib.contextmanager
def profile_span(path: Optional[str]):
    """Wrap a region in ``jax.profiler.start_trace`` (perfetto dump under
    ``path``); no-op when path is falsy. On a TPU each device op carries
    its ``jax.named_scope`` path as the ``tf_op`` stat of its event
    metadata (DESIGN.md §13; ``bench/trace_scopes.py`` reads it); the
    host-side TraceAnnotations mark the phases on the host's clock."""
    if not path:
        yield
        return
    import jax
    Path(path).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
