"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x input-shape) on the single-pod mesh, derive the three terms

  compute    = FLOPs_per_device / peak_FLOPs          (MXU)
  memory     = HBM_bytes_per_device / HBM_bandwidth   (HBM)
  collective = collective_bytes_per_device / ICI_bw   (interconnect)

from the trip-count-corrected HLO analysis (``repro.launch.hlocost`` — the
stock ``cost_analysis()`` counts scan bodies once, see that module), plus

  MODEL_FLOPS        = 6 * N(_active) * tokens  (the useful-work floor)
  MODEL_FLOPS / HLO  = fraction of compiled compute that is "useful"
                       (catches remat / densemask / rect-schedule waste)
  fit                = per-device argument bytes vs HBM capacity

Hardware constants: TPU v5e.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link (~per-chip effective here)
HBM_CAP = 16e9             # v5e HBM per chip

DRYRUN_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def comm_round_seconds(wire_bytes: float, bandwidth: float = ICI_BW) -> float:
    """Seconds one exchange round's payload spends on the slow link.

    ``wire_bytes`` is the EXACT codec-aware payload the comm subsystem
    reports (``Exchange.wire_bytes_per_round`` / the round record's
    ``wire_bytes``). Feeds ``AdaptiveT.from_comm_bytes`` — the
    measured replacement for the HLO all-reduce estimate this module
    otherwise derives r from."""
    return wire_bytes / bandwidth


def model_flops(arch: str, shape_name: str, meta: Dict) -> float:
    """Global useful FLOPs for the step: 6*N(_active)*D training tokens
    (incl. the local T_i inner steps), 2*N*D for forward-only steps."""
    from repro.configs.base import INPUT_SHAPES, get_config

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    n = cfg.n_active_params() if cfg.is_moe else cfg.n_params()
    if shape.kind == "train":
        tokens = meta.get("tokens",
                          shape.global_batch * shape.seq_len)
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def load_records(multi_pod: bool = False, tag: str = "") -> List[Dict]:
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    end = f"_{mesh}" + (f"_{tag}" if tag else "")
    recs = []
    for p in sorted(DRYRUN_DIR.glob("*.json")):
        if not p.stem.endswith(end):
            continue
        try:
            recs.append(json.loads(p.read_text()))
        except Exception:
            pass
    return recs


def roofline_row(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok" or "hlocost" not in rec:
        return None
    hc = rec["hlocost"]
    if "error" in hc:
        return None
    n_dev = rec["n_devices"]
    t_c = hc["flops"] / PEAK_FLOPS
    t_m = hc["hbm_bytes"] / HBM_BW
    t_x = hc["collective_bytes"] / ICI_BW
    slow_gb = hc.get("collective_bytes_slowlink", 0) / 1e9
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    mf = model_flops(rec["arch"], rec["shape"], rec.get("meta", {}))
    hlo_global = hc["flops"] * n_dev
    arg_b = rec.get("arg_bytes_per_device", 0)
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "mesh": "x".join(str(d) for d in rec["mesh"]),
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_frac": mf / hlo_global if hlo_global else 0.0,
        "arg_gb_per_device": arg_b / 1e9,
        "fits_hbm": arg_b <= HBM_CAP,
        "coll_by_kind": hc.get("collectives_by_kind", {}),
        "slowlink_gb": slow_gb,
        "tag": rec.get("tag", ""),
    }


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.1f}ms"
    return f"{x * 1e6:.0f}us"


def render_table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | compute | memory | collective | dominant | "
           "useful% | GB/dev | fits | x-group GB |\n"
           "|---|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {fmt_s(r['compute_s'])} | "
            f"{fmt_s(r['memory_s'])} | {fmt_s(r['collective_s'])} | "
            f"**{r['dominant']}** | {100 * r['useful_frac']:.1f} | "
            f"{r['arg_gb_per_device']:.2f} | "
            f"{'yes' if r['fits_hbm'] else 'NO'} | "
            f"{r['slowlink_gb']:.1f} |")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    rows = [r for r in (roofline_row(rec) for rec in
                        load_records(args.multi_pod, args.tag)) if r]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    if args.json:
        print(json.dumps(rows, indent=1))
    else:
        print(render_table(rows))
        out = DRYRUN_DIR.parent / (
            "roofline" + ("_mp" if args.multi_pod else "")
            + (f"_{args.tag}" if args.tag else "") + ".json")
        out.write_text(json.dumps(rows, indent=1))
        print(f"\nsaved -> {out}")


if __name__ == "__main__":
    main()
