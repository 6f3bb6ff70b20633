"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: the traced window, device busy time (the union of the
intervals in which an operation ran), device time by operation name,
collective time, and the device's idle gaps named by the host
annotation that was open during each.

    python bench/trace_reduce.py <dir or .xplane.pb>   # prints the reduction

The reduction works on plain event tuples, so it can be checked on a
small recorded trace and on hand-made events alike.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import defaultdict

# device-plane line that holds one event per executed operation (a loop
# or call is an event that contains its body's events), and the line of
# asynchronous operations (copies, collectives started and awaited)
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# host line of the Python thread, where TraceAnnotations are recorded
# (also taken: any host line that holds the harness's window annotation)
HOST_LINE = "python"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|collective-broadcast")
# the harness's own annotation around the measured window
WINDOW = "window"


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(text: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12 (fusion)``: the
    instruction's name and opcode, from an op event's HLO text."""
    head, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    return f"{head.lstrip('%')} ({m.group(1)})" if m else head.lstrip("%")


def load_events(path: str) -> dict:
    """``{"device": {plane: [(name, start_ns, end_ns, text), ...]},
    "async": {plane: [...]}, "host": [(name, start_ns, end_ns), ...]}``
    from a trace file: every operation on each accelerator plane's ops
    line and async line (``name`` is the instruction and its opcode,
    ``text`` the event's whole HLO text, for matching by shape or kind),
    and every host annotation (Python-tracer frames, named ``$...``, left
    out)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    device, asyncs, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs, aev = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                for e in line.events:
                    ev = (short_name(e.name), float(e.start_ns),
                          float(e.start_ns + e.duration_ns), e.name)
                    (evs if line.name == OPS_LINE else aev).append(ev)
            if evs:
                device[plane.name] = evs
                asyncs[plane.name] = aev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns),
                        float(e.start_ns + e.duration_ns))
                       for e in line.events
                       if not e.name.startswith("$") and e.duration_ns > 0]
                if line.name == HOST_LINE or any(
                        n == WINDOW for n, _, _ in evs):
                    host.extend(evs)
    return {"device": device, "async": asyncs, "host": host}


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def leaves(evs):
    """The events that contain no other event (a loop's or a call's own
    event spans its body's events on the same line)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    parent_of_child = set()
    stack = []
    for i in order:
        s, e = evs[i][1], evs[i][2]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            parent_of_child.add(stack[-1])
        stack.append(i)
    return [evs[i] for i in range(len(evs)) if i not in parent_of_child]


def window_of(events: dict, name: str = WINDOW):
    spans = [(s, e) for n, s, e in events["host"] if n == name]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    allev = [x for evs in events["device"].values() for x in evs]
    return min(x[1] for x in allev), max(x[2] for x in allev)


def host_segments(host, lo, hi):
    """The window cut at every host annotation's edges, each piece named
    by the innermost (shortest) annotation open over it:
    ``[(start, end, name), ...]`` in order."""
    spans = [(s, e, n) for n, s, e in host if n != WINDOW and e > lo and s < hi]
    cuts = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e)
                              if lo < x < hi})
    spans.sort()
    out, open_, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= a:
            open_.append(spans[j])
            j += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        name = (min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_
                else "(no annotation)")
        out.append((a, b, name))
    return out


def name_gaps(gaps, segments):
    """Idle time by the host annotation open while the device idled, and
    each gap with the annotation that covered most of it."""
    by = defaultdict(float)
    each = []
    j = 0
    for s, e in gaps:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k, mine = j, defaultdict(float)
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            mine[name] += min(b, e) - max(a, s)
            k += 1
        for name, d in mine.items():
            by[name] += d
        each.append((e - s, max(mine, key=mine.get) if mine
                     else "(no annotation)"))
    return by, each


def reduce(events: dict, window=None) -> dict:
    """The reduction, averaged over the accelerator planes: seconds of
    ``window``, ``busy`` (union of ops), ``ops`` (time by op name, leaf
    ops only, so a loop's time is its body's), ``texts`` (time by the
    op's HLO text, for matching by shape or kind), ``collective`` (leaf
    and async collectives) and idle time by enclosing host annotation,
    with the longest gaps."""
    lo, hi = window if window else window_of(events)
    planes = sorted(events["device"])
    n = max(len(planes), 1)
    busy = 0.0
    ops = defaultdict(float)
    texts = defaultdict(float)
    coll = 0.0
    gaps_by = defaultdict(float)
    longest = []
    for i, plane in enumerate(planes):
        evs = events["device"][plane]
        merged = union(clip([(s, e) for _, s, e, _ in evs], lo, hi))
        busy += sum(e - s for s, e in merged)
        for name, s, e, text in leaves(evs):
            c = clip([(s, e)], lo, hi)
            if not c:
                continue
            d = c[0][1] - c[0][0]
            ops[name] += d
            texts[text] += d
            if COLLECTIVE.search(name):
                coll += d
        for name, s, e, text in events.get("async", {}).get(plane, []):
            c = clip([(s, e)], lo, hi)
            if c and COLLECTIVE.search(name):
                coll += c[0][1] - c[0][0]
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
            gaps_by, longest = name_gaps(
                gaps, host_segments(events["host"], lo, hi))
    ns = 1e-9
    longest.sort(reverse=True)
    return {
        "window_s": (hi - lo) * ns,
        "n_devices": len(planes),
        "busy_s": busy / n * ns,
        "ops": {k: v / n * ns for k, v in ops.items()},
        "texts": {k: v / n * ns for k, v in texts.items()},
        "collective_s": coll / n * ns,
        "idle_by_annotation": {k: v * ns for k, v in gaps_by.items()},
        "longest_gaps": [(name, d * ns) for d, name in longest[:10]],
    }


def match_seconds(reduced: dict, patterns) -> float:
    """Device seconds of the leaf ops whose HLO text matches any of
    ``patterns`` (regular expressions)."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return sum(v for k, v in reduced["texts"].items() if rx.search(k))


def breakdown(reduced: dict) -> dict:
    top_ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(reduced["idle_by_annotation"].items(),
                      key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in top_gaps]}


if __name__ == "__main__":
    r = reduce(load_events(sys.argv[1]))
    r.pop("texts")
    r["ops"] = dict(sorted(r["ops"].items(), key=lambda kv: -kv[1])[:30])
    print(json.dumps(r, indent=1))
