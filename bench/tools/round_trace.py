#!/usr/bin/env python3
"""A small recorded trace of the training round, for the tests of the
scope readers: a cell's round cut to a few layers, warmed up, then a few
rounds under the profiler inside a ``window`` annotation, as the
benchmark's traced runs record them.

    python bench/tools/round_trace.py record --workload smollm2-train-g4t4 \
        --layers 2 --local-steps 2 --rounds 2 --out <dir>     # on the chip
    python bench/tools/round_trace.py shrink <in .xplane.pb> <out .xplane.pb>

``shrink`` keeps what ``trace_reduce.load_events`` and
``trace_scopes.read_tf_ops`` read and drops the rest: on the accelerator
planes the ops lines, each event cut to its op, start and duration, and
each op's event metadata cut to its name and its ``tf_op`` stat; on the
host the lines that carry the Python thread's annotations. It makes the
file small enough to keep with the tests.
"""
from __future__ import annotations

import argparse
import copy
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import common  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scopes as ts  # noqa: E402


def record(args) -> None:
    common.env_setup()
    import jax
    from drivers import train as drv
    cell = copy.deepcopy(common.cell_of(args.workload))
    cell["config_file"]["arch"]["n_layers"] = args.layers
    cell["traffic_file"]["local_steps"] = args.local_steps
    devs = common.devices_for(cell["chips"])
    rnd = drv.Round(cell, args.seed, devs)
    rnd.step()                           # compiles; every shape is warm
    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    common.start_trace(out)
    with common.annotate("window"):
        for _ in range(args.rounds):
            rnd.step()
    jax.profiler.stop_trace()
    print(trace_reduce.find_xplane(str(out)))


# -- shrink -----------------------------------------------------------------

# XPlane.lines; XLine.name, .events; XEvent.metadata_id, .offset_ps,
# .duration_ps (the rest of xplane.proto's numbers: trace_scopes)
PLANE_LINES, LINE_NAME, LINE_EVENTS = 3, 2, 4
EVENT_FIELDS = (1, 2, 3)


def _varint_bytes(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(num: int, payload: bytes) -> bytes:
    """A length-delimited field."""
    return _varint_bytes(num << 3 | 2) + _varint_bytes(len(payload)) \
        + payload


def _field_int(num: int, v: int) -> bytes:
    return _varint_bytes(num << 3) + _varint_bytes(v)


def _emit(num: int, v) -> bytes:
    return _field_int(num, v) if isinstance(v, int) else _field(num,
                                                                bytes(v))


def _device_plane(plane) -> bytes:
    """The ops lines with each event cut to its metadata id, offset and
    duration; the event metadata that those events use, cut to its id,
    name and ``tf_op`` stat."""
    tf_op_ids = {k for k, v in ts.stat_names(plane).items() if v == ts.TF_OP}
    lines, used = b"", set()
    for f, v in ts.fields(plane):
        if f != PLANE_LINES:
            continue
        line = list(ts.fields(v))
        name = next((ts.text(x) for lf, x in line if lf == LINE_NAME), "")
        if name not in (trace_reduce.OPS_LINE, trace_reduce.ASYNC_LINE):
            continue
        out = b""
        for lf, x in line:
            if lf == LINE_EVENTS:
                ev = dict(ts.fields(x))
                used.add(ev.get(1, 0))
                x = b"".join(_field_int(k, ev[k]) for k in EVENT_FIELDS
                             if k in ev)
            out += _emit(lf, x)
        lines += _field(f, out)
    out = b""
    for f, v in ts.fields(plane):
        if f == ts.PLANE_EVENT_MD:
            entry = dict(ts.fields(v))
            if entry.get(1, 0) not in used:
                continue
            md = b""
            for mf, mv in ts.fields(entry[2]):
                if mf == ts.EVENT_MD_STATS:
                    if dict(ts.fields(mv)).get(ts.STAT_MD_ID) in tf_op_ids:
                        md += _emit(mf, mv)
                elif mf in (1, ts.EVENT_MD_NAME):
                    md += _emit(mf, mv)
            out += _field(f, _field_int(1, entry.get(1, 0)) + _field(2, md))
        elif f != PLANE_LINES:
            out += _emit(f, v)
    return out + lines


def _host_plane(plane) -> bytes:
    names = {}
    for f, v in ts.fields(plane):
        if f == ts.PLANE_EVENT_MD:
            entry = dict(ts.fields(v))
            names[entry.get(1, 0)] = ts.text(dict(ts.fields(entry[2])).get(
                ts.EVENT_MD_NAME, b""))
    out = b""
    for f, v in ts.fields(plane):
        if f == PLANE_LINES:
            line = list(ts.fields(v))
            name = next((ts.text(x) for lf, x in line if lf == LINE_NAME),
                        "")
            events = [dict(ts.fields(x)) for lf, x in line
                      if lf == LINE_EVENTS]
            if name != trace_reduce.HOST_LINE and not any(
                    names.get(e.get(1, 0)) == trace_reduce.WINDOW
                    for e in events):
                continue
        out += _emit(f, v)
    return out


def shrink(src: str, dst: str) -> None:
    with open(trace_reduce.find_xplane(src), "rb") as f:
        space = memoryview(f.read())
    out = b""
    for f, v in ts.fields(space):
        if f != ts.SPACE_PLANES:
            continue
        name = ts.plane_name(v)
        if ts.is_device(name):
            out += _field(f, _device_plane(v))
        elif name.startswith("/host:") and name != "/host:metadata":
            out += _field(f, _host_plane(v))
    Path(dst).write_bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    rec = sub.add_parser("record")
    rec.add_argument("--workload", default="smollm2-train-g4t4")
    rec.add_argument("--layers", type=int, default=2)
    rec.add_argument("--local-steps", type=int, default=2)
    rec.add_argument("--rounds", type=int, default=2)
    rec.add_argument("--seed", type=int, default=1)
    rec.add_argument("--out", required=True)
    sh = sub.add_parser("shrink")
    sh.add_argument("src")
    sh.add_argument("dst")
    args = ap.parse_args(argv)
    if args.cmd == "record":
        record(args)
    else:
        shrink(args.src, args.dst)
    return 0


if __name__ == "__main__":
    sys.exit(main())
