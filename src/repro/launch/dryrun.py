import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                           "--xla_force_host_platform_device_count=512"
                           ).strip()

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination with abstract inputs (no allocation), record
memory/cost/collective analysis for EXPERIMENTS.md.

The lines above MUST stay first: jax locks the platform and the device
count on first init, and the production meshes need 512 host platform
devices (CPU ones — on an accelerator host the dry run stays off the
chip). Smoke
tests and benchmarks never import this module and keep seeing 1 device.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --all            # every pair, single-pod
  python -m repro.launch.dryrun --all --multi-pod
Driver mode (--all) runs each combo in a subprocess so one failure or
compile-memory spike cannot take down the sweep; results are cached
incrementally in experiments/dryrun/*.json.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _result_path(arch: str, shape: str, multi_pod: bool, tag: str) -> Path:
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    suffix = f"_{tag}" if tag else ""
    return OUT_DIR / f"{arch}_{shape}_{mesh}{suffix}.json"


def sharded_arg_bytes(args, shardings) -> int:
    """Per-device bytes of the step inputs under their shardings."""
    import jax

    total = 0

    def leafbytes(leaf, sh):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            return 0
        dt = jax.numpy.dtype(leaf.dtype)
        if sh is not None and hasattr(sh, "shard_shape"):
            shape = sh.shard_shape(tuple(shape))
        n = 1
        for d in shape:
            n *= d
        return n * dt.itemsize

    for a, s in zip(args, shardings if shardings else [None] * len(args)):
        la = jax.tree.leaves(a)
        ls = jax.tree.leaves(
            s, is_leaf=lambda x: hasattr(x, "shard_shape")) if s is not None \
            else [None] * len(la)
        if len(ls) != len(la):
            ls = [None] * len(la)
        total += sum(leafbytes(x, y) for x, y in zip(la, ls))
    return total


def run_one(arch: str, shape_name: str, multi_pod: bool, tag: str = "",
            mode: str = "localsgd", t_inner: int = 4, opt_name: str = "sgd",
            moe_impl: str = "", save_hlo: bool = False,
            policy: str = "tp", fsdp: int = 1, param_dtype: str = "",
            schedule: str = "rect", embed_impl: str = "",
            packed: bool = False, comm: str = "server",
            codec: str = "fp32", mix_rounds: int = 1,
            staleness: int = 1, impl: str = "auto",
            moment_codec: str = "fp32", downlink_codec: str = "",
            drop_rate: float = 0.0, stall_rate: float = 0.0,
            fault_seed: int = 0, overlap: bool = False,
            trace: str = "") -> dict:
    import dataclasses as _dc

    import jax

    from repro import obs
    from repro.configs.base import INPUT_SHAPES, get_config
    from repro.launch import hlo as hlomod
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_step

    cfg = get_config(arch)
    if param_dtype:
        cfg = _dc.replace(cfg, param_dtype=param_dtype)
    if embed_impl:
        cfg = _dc.replace(cfg, embed_impl=embed_impl)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod, fsdp=fsdp)
    kw = {}
    if shape.kind == "train":
        kw = {"mode": mode, "t_inner": t_inner, "opt_name": opt_name,
              "policy": policy, "schedule": schedule, "packed": packed,
              "comm": comm, "codec": codec, "mix_rounds": mix_rounds,
              "staleness": staleness, "impl": impl,
              "moment_codec": moment_codec,
              "downlink_codec": downlink_codec,
              "drop_rate": drop_rate, "stall_rate": stall_rate,
              "fault_seed": fault_seed, "overlap": overlap}
        if moe_impl:
            kw["moe_impl"] = moe_impl
    elif shape.kind == "prefill":
        kw = {"policy": policy, "schedule": schedule}
    built = build_step(cfg, shape, mesh, **kw)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "n_devices": mesh.devices.size, "tag": tag, "meta": built.meta,
        "status": "started",
    }
    # null sink when --trace is unset: phases still time through the
    # same fenced path the launchers use (DESIGN.md §13)
    tr = obs.Trace(trace or None, meta={
        "arch": arch, "shape": shape_name, "mode": mode,
        "packed": packed, "comm": comm, "codec": codec,
        "mesh": list(mesh.devices.shape)})
    with mesh:
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=getattr(built, "donate_argnums",
                                                ()))
        with tr.phase("lower"):
            lowered = jitted.lower(*built.args)
        with tr.phase("compile"):
            compiled = lowered.compile()
    phases = tr.take_phases()
    rec["lower_s"] = round(phases["lower"], 2)
    rec["compile_s"] = round(phases["compile"], 2)

    try:
        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # pragma: no cover - backend specific
        rec["memory_analysis"] = {"error": str(e)}
    rec["arg_bytes_per_device"] = sharded_arg_bytes(
        built.args, built.in_shardings)

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec["cost_analysis"] = {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "transcendentals", "optimal_seconds")
                or k.startswith("bytes accessed"))}
    except Exception as e:  # pragma: no cover
        rec["cost_analysis"] = {"error": str(e)}

    txt = compiled.as_text()
    rec["hlo_chars"] = len(txt)
    rec["collectives"] = hlomod.collective_summary(txt)
    try:
        from repro.launch import hlocost
        from repro.sharding import specs as shsp
        # slow-link boundary = the local-SGD GROUP boundary (the paper's
        # cross-group traffic): devices-per-group contiguous blocks.
        # (data=16,model=16) -> 16; (data=2,fsdp=8,model=16) -> 128;
        # multi-pod (pod,data,...) groups span pods -> same formula.
        slow_block = mesh.devices.size // max(shsp.n_groups(mesh), 1)
        rec["slow_block"] = slow_block
        rec["hlocost"] = hlocost.analyze(txt, slow_block=slow_block)
    except Exception as e:  # pragma: no cover
        rec["hlocost"] = {"error": str(e)}
    if save_hlo:
        p = _result_path(arch, shape_name, multi_pod, tag)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.with_suffix(".hlo.txt").write_text(txt)
    rec["status"] = "ok"
    tr.emit("dryrun", arch=arch, shape=shape_name,
            lower_s=rec["lower_s"], compile_s=rec["compile_s"],
            hlo_chars=rec["hlo_chars"], collectives=rec["collectives"])
    tr.close()
    return rec


def save(rec: dict, arch: str, shape: str, multi_pod: bool, tag: str):
    p = _result_path(arch, shape, multi_pod, tag)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(rec, indent=1))
    return p


def drive_all(multi_pod: bool, tag: str, force: bool, extra: list) -> int:
    """Run every (arch x shape) in subprocesses; cache results."""
    from repro.configs.base import ARCH_IDS, INPUT_SHAPES

    failures = 0
    for arch in ARCH_IDS:
        for shape in INPUT_SHAPES:
            p = _result_path(arch, shape, multi_pod, tag)
            if p.exists() and not force:
                st = json.loads(p.read_text()).get("status")
                if st == "ok":
                    print(f"[skip] {p.name}")
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if multi_pod:
                cmd.append("--multi-pod")
            if tag:
                cmd += ["--tag", tag]
            cmd += extra
            print(f"[run ] {arch} x {shape} "
                  f"({'2x16x16' if multi_pod else '16x16'})", flush=True)
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=3600)
            dt = time.time() - t0
            if r.returncode != 0:
                failures += 1
                err = (r.stderr or "")[-2000:]
                save({"arch": arch, "shape": shape, "status": "error",
                      "error": err, "tag": tag}, arch, shape, multi_pod, tag)
                print(f"[FAIL] {arch} x {shape} ({dt:.0f}s)\n{err}",
                      flush=True)
            else:
                print(f"[ ok ] {arch} x {shape} ({dt:.0f}s)", flush=True)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--mode", default="localsgd",
                    choices=["localsgd", "sync"])
    ap.add_argument("--t-inner", type=int, default=4)
    ap.add_argument("--opt", default="sgd")
    ap.add_argument("--packed", action="store_true",
                    help="flat-buffer train round (DESIGN.md §6): records "
                         "the packed engine's memory/collective profile "
                         "(sharded over the in-group axes when the mesh "
                         "has them — DESIGN.md §9)")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="packed update/codec kernels (pallas needs the "
                         "sharded packed path on multi-device meshes)")
    ap.add_argument("--comm", default="server",
                    choices=["server", "ring", "gossip", "async_stale",
                             "push_sum", "none"],
                    help="exchange topology (repro.comm, DESIGN.md §8; "
                         "push_sum is loss-tolerant ratio consensus)")
    ap.add_argument("--codec", default="fp32",
                    choices=["fp32", "fp16", "bf16", "int8", "int8z",
                             "topk"],
                    help="wire codec; int8/int8z/topk need --packed")
    ap.add_argument("--moment-codec", default="fp32",
                    choices=["fp32", "fp16", "bf16", "int8", "int8z"],
                    help="wire codec for the optimizer moment streams "
                         "(DESIGN.md §10); meta reports per-stream "
                         "wire_bytes_per_round_by_stream")
    ap.add_argument("--downlink-codec", default="",
                    choices=["", "fp32", "fp16", "bf16", "int8", "int8z"],
                    help="compress the server/async broadcast reply "
                         "independently of the uplink (DESIGN.md §11); "
                         "wire_bytes_down_per_round prices it")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered delayed mixing (DESIGN.md §14): "
                         "records the overlapped round's collective "
                         "profile (encode+mix scheduled beside the local "
                         "steps in one graph); needs --packed")
    ap.add_argument("--mix-rounds", type=int, default=1,
                    help="mixing hops per round (ring/gossip)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="bounded staleness s (async_stale)")
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="deterministic fault injection (DESIGN.md §12): "
                         "per-edge packet-drop probability in [0, 1)")
    ap.add_argument("--stall-rate", type=float, default=0.0,
                    help="per-round node stall probability in [0, 1)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultPlan mask-stream seed")
    ap.add_argument("--moe-impl", default="")
    ap.add_argument("--save-hlo", action="store_true")
    # §Perf hillclimb knobs ---------------------------------------------
    ap.add_argument("--policy", default="tp", choices=["tp", "dp"])
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--param-dtype", default="")
    ap.add_argument("--schedule", default="rect",
                    choices=["rect", "tri"])
    ap.add_argument("--embed-impl", default="",
                    choices=["", "onehot", "gather"])
    ap.add_argument("--trace", default="",
                    help="append lower/compile phase records to this "
                         "JSONL sink (single-run mode; --all subprocesses "
                         "would clobber one file)")
    args = ap.parse_args()
    if args.impl != "auto" and not args.packed:
        ap.error("--impl selects the packed fused kernels; add --packed")
    if args.trace and args.all:
        ap.error("--trace is single-run only; --all runs subprocesses")

    if args.all:
        extra = []
        if args.mode != "localsgd":
            extra += ["--mode", args.mode]
        if args.t_inner != 4:
            extra += ["--t-inner", str(args.t_inner)]
        if args.opt != "sgd":
            extra += ["--opt", args.opt]
        if args.moe_impl:
            extra += ["--moe-impl", args.moe_impl]
        if args.comm != "server":
            extra += ["--comm", args.comm]
        if args.codec != "fp32":
            extra += ["--codec", args.codec]
        if args.moment_codec != "fp32":
            extra += ["--moment-codec", args.moment_codec]
        if args.downlink_codec:
            extra += ["--downlink-codec", args.downlink_codec]
        if args.mix_rounds != 1:
            extra += ["--mix-rounds", str(args.mix_rounds)]
        if args.staleness != 1:
            extra += ["--staleness", str(args.staleness)]
        if args.drop_rate:
            extra += ["--drop-rate", str(args.drop_rate)]
        if args.stall_rate:
            extra += ["--stall-rate", str(args.stall_rate)]
        if args.fault_seed:
            extra += ["--fault-seed", str(args.fault_seed)]
        if args.overlap:
            extra += ["--overlap"]
        if args.impl != "auto":
            extra += ["--impl", args.impl]
        sys.exit(1 if drive_all(args.multi_pod, args.tag, args.force,
                                extra) else 0)

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    try:
        rec = run_one(args.arch, args.shape, args.multi_pod, args.tag,
                      mode=args.mode, t_inner=args.t_inner,
                      opt_name=args.opt, moe_impl=args.moe_impl,
                      save_hlo=args.save_hlo, policy=args.policy,
                      fsdp=args.fsdp, param_dtype=args.param_dtype,
                      schedule=args.schedule, embed_impl=args.embed_impl,
                      packed=args.packed, comm=args.comm, codec=args.codec,
                      mix_rounds=args.mix_rounds, staleness=args.staleness,
                      impl=args.impl, moment_codec=args.moment_codec,
                      downlink_codec=args.downlink_codec,
                      drop_rate=args.drop_rate,
                      stall_rate=args.stall_rate,
                      fault_seed=args.fault_seed, overlap=args.overlap,
                      trace=args.trace)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "status": "error",
               "error": traceback.format_exc()[-4000:], "tag": args.tag}
        save(rec, args.arch, args.shape, args.multi_pod, args.tag)
        print(rec["error"], file=sys.stderr)
        sys.exit(1)
    p = save(rec, args.arch, args.shape, args.multi_pod, args.tag)
    print(json.dumps({k: rec[k] for k in
                      ("arch", "shape", "mesh", "compile_s", "collectives")
                      if k in rec}, indent=1))
    print(f"saved -> {p}")


if __name__ == "__main__":
    main()
