"""Device time by the program's named scopes.

``jax.named_scope`` and JAX's transforms write each op's name stack into
its HLO metadata (``op_name``, e.g. ``jit(round_)/local_steps/while/body/
closed_call/vmap(fwd_bwd)/transpose(jvp())/...``). A TPU profile keeps
it as the ``tf_op`` stat of the op's event *metadata* in the
``.xplane.pb`` file, not as a stat of the op's events, and
``jax.profiler.ProfileData`` (what ``trace_reduce.load_events`` reads)
shows only the events. ``read_tf_ops`` reads the metadata with a plain
reader of the protobuf wire format over the few ``XSpace`` fields it
needs, so it needs no package beyond Python's.

Attribution rule (``scopes_of``):

- each leaf op's device seconds in the window, averaged over the chips
  (``trace_reduce.reduce``'s ``texts``), go to the scope path of its
  ``tf_op``, looked up by the op's HLO text (the metadata's name);
- a fusion carries its root op's metadata, so it takes the root's scope;
- an op that XLA inserts (a copy, a loop's bookkeeping) takes whatever
  metadata XLA gave it, and time with no ``tf_op`` stays under the empty
  path ``""``;
- the scope path is the ``tf_op`` without its trailing ``:<type>`` and
  its last segment (the primitive), each transform wrapper unwrapped
  (``vmap(transpose(jvp(fwd_bwd)))`` -> ``fwd_bwd``; an empty one, such
  as ``transpose(jvp())``, dropped), and the segments of control flow
  and remat dropped (``while``, ``body``, ``cond``, ``closed_call``,
  ``checkpoint``, ``rematted_computation``); ``jit(<name>)`` segments
  stay. ``scope_seconds(reduced, "local_steps", "fwd_bwd")`` sums the
  paths that hold those segments in that order.

    python bench/trace_scopes.py <dir or .xplane.pb>   # seconds by path
"""
from __future__ import annotations

import functools
import glob
import json
import os
import re
import sys
from collections import defaultdict

import trace_reduce

# XSpace.planes; XPlane.name, .event_metadata (map entry: key 1, value
# 2), .stat_metadata; XEventMetadata.name, .stats; XStat.metadata_id,
# .str_value, .ref_value; XStatMetadata.id, .name
# (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_MD, PLANE_STAT_MD = 2, 4, 5
EVENT_MD_NAME, EVENT_MD_STATS = 2, 5
STAT_MD_ID, STAT_STR, STAT_REF = 1, 5, 7
STAT_MD_NAME = 2
TF_OP = "tf_op"

DROPPED = frozenset({"while", "body", "cond", "closed_call", "checkpoint",
                     "rematted_computation"})
WRAPPED = re.compile(r"^(?!jit\()[A-Za-z_]\w*\((.*)\)$")


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def map_value(entry):
    """The value of a protobuf map entry (field 2)."""
    return next((v for f, v in fields(entry) if f == 2), b"")


def plane_name(plane) -> str:
    return next((text(v) for f, v in fields(plane) if f == PLANE_NAME), "")


def is_device(name: str) -> bool:
    """An accelerator plane, as ``trace_reduce.load_events`` picks them."""
    return name.startswith("/device:") and "CPU" not in name


def stat_names(plane) -> dict:
    """``{stat metadata id: stat name}`` of one plane."""
    out = {}
    for f, v in fields(plane):
        if f == PLANE_STAT_MD:
            md = dict(fields(map_value(v)))
            out[md.get(STAT_MD_ID, 0)] = text(md.get(STAT_MD_NAME, b""))
    return out


def _plane_tf_ops(plane) -> dict:
    names = stat_names(plane)
    tf_op_ids = {k for k, v in names.items() if v == TF_OP}
    out = {}
    for f, v in fields(plane):
        if f != PLANE_EVENT_MD:
            continue
        name, tf_op = None, None
        for mf, mv in fields(map_value(v)):
            if mf == EVENT_MD_NAME:
                name = text(mv)
            elif mf == EVENT_MD_STATS:
                st = dict(fields(mv))
                if st.get(STAT_MD_ID) in tf_op_ids:
                    if STAT_STR in st:
                        tf_op = text(st[STAT_STR])
                    elif STAT_REF in st:
                        tf_op = names.get(st[STAT_REF], "")
        # a text met twice keeps the first scope it was given
        if name is not None and tf_op is not None:
            out.setdefault(name, tf_op)
    return out


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime: float) -> dict:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in fields(space):
        if f == SPACE_PLANES and is_device(name := plane_name(plane)):
            ops = _plane_tf_ops(plane)
            if ops:
                out[name] = ops
    return out


def read_tf_ops(path: str) -> dict:
    """``{device plane: {HLO text: tf_op}}`` from a trace file (or the
    newest one under a directory): the ``tf_op`` stat of each op's event
    metadata on each accelerator plane, keyed by the metadata's name,
    which is the HLO text ``trace_reduce.load_events`` keys events by."""
    path = trace_reduce.find_xplane(str(path))
    return _read(path, os.path.getmtime(path))


def scope_path(tf_op: str) -> str:
    """The scope path of an op's ``tf_op`` (the module docstring's rule),
    its segments joined by ``/``."""
    head, sep, tail = tf_op.rpartition(":")
    if sep and "/" not in tail:
        tf_op = head
    out = []
    for seg in tf_op.split("/")[:-1]:
        m = WRAPPED.match(seg)
        while m:
            seg = m.group(1)
            m = WRAPPED.match(seg)
        if seg and seg not in DROPPED:
            out.append(seg)
    return "/".join(out)


def scopes_of(reduced: dict, tf_ops: dict) -> dict:
    """Device seconds of the window's leaf ops by scope path, averaged
    over the chips, from ``trace_reduce.reduce``'s ``texts``; ops with no
    ``tf_op`` under ``""``."""
    by_text = {}
    for ops in tf_ops.values():
        for hlo, tf_op in ops.items():
            by_text.setdefault(hlo, tf_op)
    out = defaultdict(float)
    for hlo, secs in reduced["texts"].items():
        tf_op = by_text.get(hlo)
        out[scope_path(tf_op) if tf_op else ""] += secs
    return dict(out)


def run_trace():
    """The trace file of the benchmark run in progress (``run.py`` keeps
    it under ``bench_out/trace/<cell>/`` while the metrics are read), or
    None."""
    import common
    found = glob.glob(str(common.OUT_DIR / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def with_scopes(ctx: dict) -> dict:
    """The context's reduced trace with ``scopes`` added (seconds by scope
    path), read from the run's trace file; a reduction that has them
    already is returned as it is, one with no trace file gets none."""
    reduced = ctx["reduced"]
    if "scopes" in reduced:
        return reduced
    path = run_trace()
    tf_ops = read_tf_ops(path) if path else {}
    return dict(reduced, scopes=scopes_of(reduced, tf_ops))


def holds(path: str, segments) -> bool:
    """Whether the scope path holds ``segments`` in this order."""
    it = iter(path.split("/"))
    return all(s in it for s in segments)


def scope_seconds(reduced: dict, *segments: str) -> float:
    """Device seconds of the scope paths that hold ``segments`` in this
    order (``reduced`` with ``scopes``, as ``with_scopes`` gives it)."""
    return sum(v for k, v in reduced["scopes"].items()
               if holds(k, segments))


def train_ms(ctx: dict, per: str, *scopes) -> float | None:
    """Milliseconds a local step (``per="step"``) or a round
    (``per="round"``) of the traced training window spent in the
    ``scopes`` (each a tuple of segments for ``scope_seconds``), averaged
    over the chips; None outside training or where none of it ran."""
    if ctx.get("kind") != "train" or ctx["rounds"] <= 0:
        return None
    reduced = with_scopes(ctx)
    secs = sum(scope_seconds(reduced, *s) for s in scopes)
    if secs <= 0:
        return None
    n = ctx["rounds"] * (ctx["traffic"]["local_steps"] if per == "step"
                         else 1)
    return 1000.0 * secs / n


if __name__ == "__main__":
    path = trace_reduce.find_xplane(sys.argv[1])
    r = trace_reduce.reduce(trace_reduce.load_events(path))
    scopes = scopes_of(r, read_tf_ops(path))
    print(json.dumps({"busy_s": r["busy_s"], "scopes": dict(
        sorted(scopes.items(), key=lambda kv: -kv[1])[:40])}, indent=1))
