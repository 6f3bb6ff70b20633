"""Observability subsystem (DESIGN.md §13).

Three layers, one schema:

* device-side round metrics — the localsgd rounds emit a UNIFORM metric
  block every round (consensus distance, per-stream codec error mass,
  push-sum backlog mass, participation/delivery) regardless of
  topology/codec/fault configuration, so downstream consumers never
  branch on which keys exist; the exact wire-byte counts come from
  shapes on the host (``round_.wire_bytes(state_G)``) and the caller
  merges them into the same record;
* host-side phase tracing — ``Trace``/``Trace.phase`` fences with
  ``jax.block_until_ready`` before reading the clock (async dispatch
  makes unfenced deltas lies), annotates phases for the profiler, and
  appends structured JSONL records;
* reporting — ``repro.obs.report`` summarizes/validates a trace file;
  the benchmarks route their timing through the same sink.
"""
from repro.obs.trace import (PhaseTimer, Trace, exchange_phases,  # noqa: F401
                             profile_span, to_jsonable)

# bump when the JSONL record layout changes incompatibly; report.py
# refuses to --check traces from a different major schema
SCHEMA_VERSION = 1

# keys present in EVERY localsgd round's metrics dict, every
# configuration (the uniform contract, DESIGN.md §13). Per-stream keys
# ride alongside: wire_bytes/<stream> and codec_err/<stream> for every
# stream the round exchanges (params + averaged moment buffers).
# A packed round built with ``loss_has_aux`` adds the loss's stats:
# ``expert_load`` (G, n_layers) for a model with experts.
ROUND_KEYS = (
    "loss", "grad_sq", "inner_steps",
    "wire_bytes", "wire_bytes_up", "wire_bytes_down",
    "wire_bytes_intra", "wire_bytes_inter",
    "consensus_sq", "consensus_sq_post",
    "backlog_mass", "participation", "delivery_rate",
    "participation_intra", "participation_inter",
    "delivery_rate_intra", "delivery_rate_inter",
)


def round_metric_keys(streams=("params",)):
    """The full uniform key set for a round exchanging ``streams``."""
    per = tuple(f"wire_bytes/{s}" for s in streams)
    per += tuple(f"codec_err/{s}" for s in streams)
    return ROUND_KEYS + per


def streams_of(metrics) -> tuple:
    """Recover the stream names from a round record's metric keys."""
    return tuple(sorted(k.split("/", 1)[1] for k in metrics
                        if k.startswith("wire_bytes/")))
