"""Training launcher: the paper's local-SGD schedule (or the sync-DP
baseline) on any assigned architecture.

On this CPU container run reduced configs:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --reduced \
      --rounds 10 --t-inner 4
On an accelerator host the same entry point runs the full config; the
G groups spread over the first G*S devices whenever the process sees
that many (one group per chip on a four-chip host at --shard 1).
``main(argv)`` also returns the run's record (see its docstring).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import comm as comm_mod
from repro import obs
from repro import optim
from repro.checkpoint import io as ckpt_io
from repro.configs.base import get_config
from repro.core import localsgd as lsgd
from repro.core.controller import AdaptiveT, OnlineT
from repro.data.synthetic import TokenPipeline
from repro.launch import compile_cache
from repro.models import build_model
from repro.optim import packing


def add_modalities(batch, cfg, rng):
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(rng.randn(
            *batch["tokens"].shape[:-1], cfg.n_patches, cfg.d_model)
            .astype(np.float32))
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(rng.randn(
            *batch["tokens"].shape[:-1], cfg.n_frames, cfg.d_model)
            .astype(np.float32))
    return batch


def calibrate_fences(loss_fn, opt, lcfg, layout, exchange, sexec, params,
                     batch, n_groups):
    """Measure the two references ``obs.exchange_phases`` derives the
    honest exchange-time split from (DESIGN.md §14): the SAME round
    built with comm='none' gives the pure-local-compute time, and (in
    overlap mode) the barrier variant of the same exchange gives the
    standalone exchange cost — both fenced, best of two runs after a
    warmup. Returns ``(local_ref_per_step_s, exch_ref_s)``; the local
    reference scales linearly in T when the controller later changes it,
    so one calibration covers the whole run."""

    def best_round_s(exch):
        rnd = jax.jit(lsgd.make_local_round(loss_fn, opt, lcfg,
                                            layout=layout, exchange=exch,
                                            shardexec=sexec))
        st = lsgd.init_state(params, opt, n_groups=n_groups,
                             layout=layout, exchange=exch)
        st, m = rnd(st, batch)
        jax.block_until_ready(m)
        best = float("inf")
        for _ in range(2):
            with obs.PhaseTimer() as t:
                st, m = t(rnd(st, batch))
            best = min(best, t.seconds)
        return best

    local_ref_s = best_round_s(comm_mod.get_exchange("none", "fp32",
                                                     n_groups))
    exch_ref_s = 0.0
    if exchange.overlap:
        import dataclasses
        barrier = dataclasses.replace(exchange, overlap=False)
        exch_ref_s = max(0.0, best_round_s(barrier) - local_ref_s)
    return local_ref_s / max(lcfg.inner_steps, 1), exch_ref_s


def custom_calls(hlo: str) -> int:
    """Number of compiled Pallas TPU kernels in an HLO text."""
    return hlo.count('custom_call_target="tpu_custom_call"')


def main(argv=None, devices=None) -> dict:
    """Run the trainer on ``argv`` (default: the command line).

    ``devices`` (default ``jax.devices()``) is the device list groups are
    placed on. Returns the run's record: ``history`` (per-round mean loss
    and grad_sq), ``compile_s`` and ``hlo`` of the first compiled round
    (local-SGD mode), the final ``state`` and the server ``params``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lenet")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="localsgd",
                    choices=["localsgd", "sync"])
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--per-group", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--t-inner", type=int, default=4)
    ap.add_argument("--t-i", default="",
                    help="comma-separated per-node T_i (paper Alg 1), "
                         "e.g. --t-i 1,4,8,16; max becomes the scan bound")
    ap.add_argument("--threshold", type=float, default=None,
                    help="T_i=inf mode: local steps until ||g||^2<=eps")
    ap.add_argument("--adaptive-t", nargs="?", const="static", default="",
                    choices=["static", "online"],
                    help="T controller: 'static' (bare --adaptive-t, the "
                         "Sec-4 fit from the decay trajectory alone) or "
                         "'online' (DESIGN.md §14: re-estimates the cost "
                         "ratio from fenced phase times and scales T by "
                         "the measured consensus contraction each round)")
    ap.add_argument("--cost-ratio", type=float, default=0.01,
                    help="r = C_g/C_c for the adaptive controller "
                         "(online mode uses it as the prior and refines "
                         "it from measured phase times)")
    ap.add_argument("--opt", default="sgd")
    ap.add_argument("--packed", action="store_true",
                    help="flat-buffer fast path: fused whole-model updates"
                         " on one (G, N) f32 buffer (see DESIGN.md)")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="packed update/codec kernels: fused Pallas "
                         "kernels or the jnp fusion (DESIGN.md §6/§9)")
    ap.add_argument("--shard", type=int, default=1,
                    help="in-group shard count S (packed localsgd only): "
                         "shards the flat buffer over a (G, S) device "
                         "mesh and runs the fused/codec kernels in "
                         "shard_map blocks on the local shards "
                         "(DESIGN.md §9; needs G*S devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    ap.add_argument("--comm", "--topology", dest="comm", default="server",
                    choices=["server", "ring", "gossip", "async_stale",
                             "push_sum", "hierarchical", "none"],
                    help="exchange topology (repro.comm, DESIGN.md §8; "
                         "push_sum is loss-tolerant ratio consensus, "
                         "DESIGN.md §12; hierarchical is the two-tier "
                         "pod/DCN factoring, DESIGN.md §16)")
    ap.add_argument("--n-pods", type=int, default=0,
                    help="hierarchical only: pod count P; must divide "
                         "--groups (pods of G/P nodes, DESIGN.md §16)")
    ap.add_argument("--intra-topology", default="ring",
                    choices=["ring", "server"],
                    help="hierarchical within-pod stage (reliable "
                         "interconnect tier)")
    ap.add_argument("--inter-topology", default="push_sum",
                    choices=["push_sum", "server"],
                    help="hierarchical cross-pod stage: push_sum ratio "
                         "consensus over the lossy DCN, or the reliable "
                         "parameter-server baseline")
    ap.add_argument("--inter-codec", default="",
                    choices=["", "fp32", "fp16", "bf16", "int8", "int8z"],
                    help="independent wire codec for the cross-pod tier "
                         "(DESIGN.md §16); default: same as --codec. "
                         "int8/int8z need --inter-topology server")
    ap.add_argument("--intra-drop-rate", type=float, default=0.0,
                    help="hierarchical: per-edge drop probability on the "
                         "within-pod tier (its own seed lane; --drop-rate "
                         "arms the cross-pod tier)")
    ap.add_argument("--intra-stall-rate", type=float, default=0.0,
                    help="hierarchical: per-round node stall probability "
                         "on the within-pod tier")
    ap.add_argument("--codec", default="fp32",
                    choices=["fp32", "fp16", "bf16", "int8", "int8z",
                             "topk"],
                    help="wire codec for the model exchange; int8/int8z/"
                         "topk need --packed (the flat buffer is the "
                         "wire format)")
    ap.add_argument("--moment-codec", default="fp32",
                    choices=["fp32", "fp16", "bf16", "int8", "int8z"],
                    help="wire codec for the optimizer moment streams "
                         "(DESIGN.md §10); int8/int8z need --packed, "
                         "topk is refused for moments; int8z is the "
                         "zero-preserving moment-friendly variant "
                         "(DESIGN.md §10/§14)")
    ap.add_argument("--downlink-codec", default="",
                    choices=["", "fp32", "fp16", "bf16", "int8", "int8z"],
                    help="compress the server/async broadcast reply "
                         "independently of the uplink codec (DESIGN.md "
                         "§11); default: idealized broadcast priced at "
                         "uplink widths (the pre-§11 behavior, bit-exact)")
    ap.add_argument("--hop-impl", default="ppermute",
                    choices=["ppermute", "allgather"],
                    help="sharded ring/gossip hop collective (DESIGN.md "
                         "§11): ppermute neighbor exchange (O(deg*shard) "
                         "wire) or the dense all_gather reference")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered delayed mixing (DESIGN.md "
                         "§14): the previous round's payload mixes while "
                         "this round's local steps run; needs --packed "
                         "and a server/ring/gossip topology")
    ap.add_argument("--mix-rounds", type=int, default=1,
                    help="mixing hops per round (ring/gossip)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="bounded staleness s (async_stale)")
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="deterministic fault injection (DESIGN.md §12): "
                         "per-edge packet-drop probability in [0, 1); "
                         "0 keeps the exchange bit-exact fault-free")
    ap.add_argument("--stall-rate", type=float, default=0.0,
                    help="per-round node stall probability in [0, 1) "
                         "(a stalled node skips the exchange entirely)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the FaultPlan mask stream — faults are "
                         "a pure function of (round, seed), so reruns and "
                         "checkpoint resumes replay the same faults")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--trace", default="",
                    help="append phase-fenced JSONL round records here "
                         "(DESIGN.md §13); summarize/validate with "
                         "PYTHONPATH=src python -m repro.obs.report")
    ap.add_argument("--profile", default="",
                    help="dump a perfetto trace of the run under this "
                         "directory (jax.profiler.start_trace)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.mode == "sync" and (args.comm != "server"
                                or args.codec != "fp32"
                                or args.moment_codec != "fp32"
                                or args.downlink_codec or args.overlap
                                or args.drop_rate or args.stall_rate
                                or args.n_pods or args.inter_codec
                                or args.intra_drop_rate
                                or args.intra_stall_rate):
        ap.error("--comm/--codec/--drop-rate select the local-SGD model "
                 "exchange; sync-DP all-reduces gradients every step and "
                 "has no exchange to configure")
    if args.impl != "auto" and not args.packed:
        ap.error("--impl selects the packed fused kernels; add --packed")
    if args.shard > 1 and not (args.packed and args.mode == "localsgd"):
        ap.error("--shard shards the packed flat buffer over a mesh; it "
                 "needs --packed and --mode localsgd")
    if args.overlap and not args.packed:
        ap.error("--overlap double-buffers the packed flat stream payload "
                 "(comm['inflight'], DESIGN.md §14); add --packed")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, schedule="rect")
    params = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M mode={args.mode}")

    # one Trace regardless of --trace: the null sink still fences every
    # phase with block_until_ready, so printed timings are honest even
    # when nothing is written (DESIGN.md §13)
    trace = obs.Trace(args.trace or None, meta={
        "arch": cfg.name, "mode": args.mode, "groups": args.groups,
        "t_inner": args.t_inner, "comm": args.comm, "codec": args.codec,
        "rounds": args.rounds, "n_params": n_params,
        "packed": bool(args.packed), "shard": args.shard,
        "overlap": bool(args.overlap), "adaptive_t": args.adaptive_t,
        "drop_rate": args.drop_rate, "stall_rate": args.stall_rate})

    layout = packing.layout_of(params) if args.packed else None
    G = args.groups
    mesh, sexec = None, None
    n_dev = G * args.shard
    devices = list(devices if devices is not None else jax.devices())
    if args.shard > 1 and len(devices) < n_dev:
        raise SystemExit(
            f"--shard {args.shard} with --groups {G} needs {n_dev} "
            f"devices, found {len(devices)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev}")
    if (args.packed and args.mode == "localsgd" and n_dev > 1
            and len(devices) >= n_dev):
        # groups (and in-group shards) over the first G*S devices: the
        # paper's deployment, one group per node
        from jax.sharding import Mesh
        from repro.sharding import shardexec as shx

        mesh = Mesh(np.array(devices[:n_dev]).reshape(G, args.shard),
                    ("data", "model"))
        sexec = shx.plan_for(mesh, require=True, hop_impl=args.hop_impl)
        layout = packing.shard_layout(layout, sexec.n_shards)
        print(f"sharded execution: G={G} x {args.shard} shards on "
              f"{n_dev} devices, buffer {layout.size} -> {layout.padded} "
              f"padded ({layout.shard_size}/shard)")
    opt = optim.get(args.opt, args.lr, packed=args.packed,
                    **({"impl": args.impl} if args.packed else {}))
    pipe = TokenPipeline(cfg.vocab_size, args.seq, seed=args.seed)
    rng = np.random.RandomState(args.seed)
    history = []
    compile_s, hlo = None, ""

    if args.mode == "sync":
        step = jax.jit(lsgd.make_sync_step(model.loss, opt, layout=layout),
                       donate_argnums=(0,))
        state = lsgd.init_state(params, opt, layout=layout)
        batches = pipe.batches((G * args.per_group,))
        with obs.profile_span(args.profile):
            for n in range(args.rounds):
                with trace.phase("data"):
                    batch = add_modalities(
                        {"tokens": jnp.asarray(next(batches)["tokens"])},
                        cfg, rng)
                with trace.phase("step") as f:
                    state, m = f(step(state, batch))
                rec = trace.emit_round(n, m, kind="step")
                history.append({"loss": float(m["loss"]),
                                "grad_sq": float(m["grad_sq"])})
                if n % args.log_every == 0:
                    print(f"step {n:4d} loss {history[-1]['loss']:.4f} "
                          f"gsq {history[-1]['grad_sq']:.3e} "
                          f"({rec['phase_s'].get('step', 0.0):.2f}s)")
        final = (packing.unpack(state["params"], layout)
                 if args.packed else state["params"])
    else:
        t_i = None
        t_inner = args.t_inner
        if args.t_i:
            t_i = tuple(int(v) for v in args.t_i.split(","))
            assert len(t_i) == G, (t_i, G)
            t_inner = max(t_i)
        # the packed hot path skips per-step metric trajectories unless
        # the adaptive-T controller needs them
        metrics = "traj" if args.adaptive_t else "final"
        exchange = comm_mod.get_exchange(
            args.comm, args.codec, G, mix_rounds=args.mix_rounds,
            staleness=args.staleness,
            impl=args.impl if args.packed else "auto",
            moment_codec=args.moment_codec,
            downlink_codec=args.downlink_codec,
            drop_rate=args.drop_rate, stall_rate=args.stall_rate,
            fault_seed=args.fault_seed, overlap=args.overlap,
            n_pods=args.n_pods, intra_topology=args.intra_topology,
            inter_topology=args.inter_topology,
            inter_codec=args.inter_codec,
            intra_drop_rate=args.intra_drop_rate,
            intra_stall_rate=args.intra_stall_rate)
        # every topology averages opt state now that the per-stream
        # staleness buffers exist (DESIGN.md §10)
        avg_opt = exchange.supports_opt_state_averaging
        lcfg = lsgd.LocalSGDConfig(
            n_groups=G, inner_steps=t_inner, t_i=t_i,
            threshold=args.threshold, max_inner=500, metrics=metrics,
            average_opt_state=avg_opt)
        rnd = jax.jit(lsgd.make_local_round(model.loss_and_stats, opt, lcfg,
                                            layout=layout,
                                            exchange=exchange,
                                            shardexec=sexec,
                                            loss_has_aux=True),
                      donate_argnums=(0,))
        state = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                                exchange=exchange,
                                average_opt_state=avg_opt)
        if sexec is not None:
            # place the buffers on the mesh once; donation keeps every
            # subsequent round's state resident in place
            buf_sh = NamedSharding(mesh, sexec.buf_spec())
            rep_sh = NamedSharding(mesh, P())
            state = jax.tree.map(
                lambda x: jax.device_put(
                    x, buf_sh if (x.ndim == 2
                                  and x.shape[-1] == layout.padded)
                    else rep_sh), state)
        batches = pipe.batches((G, args.per_group))
        batch_sh = None
        if sexec is not None:
            batch_sh = NamedSharding(mesh, sexec.group_spec())
        # exact wire counts come from shapes, on the host (an int32 jit
        # output would wrap at full width)
        wire = rnd.wire_bytes(state)
        # on a lossy network each useful round costs a full attempt's
        # worth of link time (AdaptiveT.from_exchange's delivery_rate
        # repricing): comm is 1/delivery more expensive, so r shrinks
        # and the controller pushes T* up — fewer, longer rounds
        ctl = None
        if args.adaptive_t == "online":
            # DESIGN.md §14: the prior r is refined online from the
            # calibrated fences; the delivery repricing still applies
            ctl = OnlineT(r=args.cost_ratio * exchange.delivery_rate)
        elif args.adaptive_t:
            ctl = AdaptiveT(r=args.cost_ratio * exchange.delivery_rate)
        t_cur = args.t_inner
        wire_total = 0
        # the exchange-time split needs the packed path's uniform round
        # shape to calibrate against; pytree rounds skip it (the
        # report's phase gate is conditional on the keys being present)
        calibrate = args.packed and (args.overlap or bool(args.trace)
                                     or args.adaptive_t == "online")
        local_ref_step = exch_ref_s = 0.0
        trace.meta.update({"comm": exchange.name,
                           "delivery_rate": exchange.delivery_rate})
        if args.packed:
            # where the round crosses between its flat buffers and the
            # leaves, and how often (DESIGN.md §6)
            trace.meta.update({"buffer_path": rnd.buffer_path,
                               "buffer_passes": rnd.buffer_passes})
        with obs.profile_span(args.profile):
            for n in range(args.rounds):
                with trace.phase("data"):
                    batch = add_modalities(
                        {"tokens": jnp.asarray(next(batches)["tokens"])},
                        cfg, rng)
                    if batch_sh is not None:
                        batch = jax.device_put(batch, batch_sh)
                if calibrate and n == 0:
                    local_ref_step, exch_ref_s = calibrate_fences(
                        model.loss, opt, lcfg, layout, exchange, sexec,
                        params, batch, G)
                if ctl is not None and t_cur != lcfg.inner_steps:
                    lcfg = lsgd.LocalSGDConfig(
                        n_groups=G, inner_steps=t_cur, max_inner=500,
                        metrics=metrics, average_opt_state=avg_opt)
                    rnd = jax.jit(lsgd.make_local_round(
                        model.loss_and_stats, opt, lcfg, layout=layout,
                        exchange=exchange, shardexec=sexec,
                        loss_has_aux=True),
                        donate_argnums=(0,))
                if compile_s is None:
                    # compile the first round ahead of time to time it and
                    # keep its HLO; the jitted call below reuses that
                    # executable, and retraces whenever the state's shapes
                    # change (e.g. the t_i count promotion of round 0)
                    t0 = time.perf_counter()
                    lowered = rnd.lower(state, batch)
                    t1 = time.perf_counter()
                    hlo = lowered.compile().as_text()
                    compile_s = time.perf_counter() - t1
                    print(f"round traced in {t1 - t0:.2f}s, compiled in "
                          f"{compile_s:.2f}s ({custom_calls(hlo)} Pallas "
                          "TPU kernel calls)")
                with trace.phase("round") as f:
                    state, m = f(rnd(state, batch))
                m = {**m, **wire}
                t_used = int(jnp.max(m["inner_steps"]))
                fences = None
                if calibrate:
                    fences = obs.exchange_phases(
                        trace.phase_seconds("round"),
                        local_ref_step * t_used, exch_ref_s,
                        overlap=args.overlap)
                    for k, v in fences.items():
                        trace.add_phase(k, v)
                if ctl is not None and "grad_sq_traj" in m:
                    traj = np.asarray(m["grad_sq_traj"])[0]
                    if isinstance(ctl, OnlineT):
                        cerr = sum(float(jnp.mean(v))
                                   for k, v in m.items()
                                   if k.startswith("codec_err/"))
                        t_cur = ctl.update(
                            traj, t_used=t_used,
                            local_s=(local_ref_step * t_used) or None,
                            exchange_s=(fences or {}).get(
                                "exchange_total") or None,
                            consensus_pre=float(
                                jnp.mean(m["consensus_sq"])),
                            consensus_post=float(
                                jnp.mean(m["consensus_sq_post"])),
                            codec_err=cerr)
                    else:
                        t_cur = ctl.update(traj)
                rec = trace.emit_round(n, m)
                wire_total += wire["wire_bytes"]
                history.append({"loss": float(jnp.mean(m["loss"])),
                                "grad_sq": float(jnp.mean(m["grad_sq"]))})
                if n % args.log_every == 0:
                    print(f"round {n:4d} "
                          f"loss {history[-1]['loss']:.4f} "
                          f"gsq {history[-1]['grad_sq']:.3e} "
                          f"T {t_used} "
                          f"wire {wire['wire_bytes']:,}B "
                          f"part {float(m['participation']):.2f} "
                          f"cons {float(jnp.mean(m['consensus_sq'])):.3e} "
                          f"({rec['phase_s'].get('round', 0.0):.2f}s)")
        print(f"comm {exchange.name}: {wire_total:,} wire bytes over "
              f"{args.rounds} rounds")
        final = lsgd.server_params(state, layout=layout)

    if args.checkpoint:
        with trace.phase("checkpoint"):
            ckpt_io.save(args.checkpoint, final,
                         metadata={"arch": cfg.name, "rounds": args.rounds,
                                   "mode": args.mode})
        trace.emit("checkpoint", path=args.checkpoint,
                   seconds=round(trace.take_phases()["checkpoint"], 6))
        print(f"checkpoint -> {args.checkpoint}.npz")
    trace.close()
    if args.trace:
        print(f"trace -> {args.trace} ({trace.n_records} records)")
    return {"history": history, "compile_s": compile_s, "hlo": hlo,
            "state": state, "params": final}


if __name__ == "__main__":
    main()
