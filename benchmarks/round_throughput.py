"""Round-engine throughput: packed flat-buffer vs per-leaf pytree rounds.

Measures steps/sec (T inner steps per local round, G groups) and bytes
moved for the implementations of the paper's hot path — the T-step local
loop + one server averaging (core.localsgd):

  pytree       the seed engine as shipped: per-leaf python-zipped updates
               and per-step loss/||grad||^2 trajectory metrics
  packed       the flat-buffer engine, default contract: one (G, N) f32
               buffer per state part, one fused update pass per step, one
               flat mean over G, metrics evaluated ONCE on the round's
               result (the fixed-T algorithm needs no per-step
               diagnostics), donated buffers
  packed_traj  the flat-buffer engine in metric-parity mode (per-step
               trajectories like the seed) — separates the two sources of
               the win: fused flat updates vs the leaner metric contract.
               On this 2-core CPU container XLA already fuses the per-leaf
               chains to the bandwidth floor, so packed_traj ties the seed
               (~1.0x) and the headline win comes from not materializing
               T per-step trajectories; on TPU the fused Pallas kernels
               are expected to widen both numbers.

The probe loss is separable (grad_i = p_i - target, leaf by leaf), so its
forward/backward is the SAME per-leaf work in both engines: what the
numbers compare is exactly the round engine this PR rewires (optimizer
update + metrics + averaging). A full model fwd/bwd is identical code in
both paths and would only dilute the signal. HONEST CAVEAT — the BENCH
JSON's ``real_model`` row, measured with the actual transformer loss on
this CPU container, shows packed at ~0.8-1.0x: fwd/bwd dominates there
and the per-step grad pack adds passes, so on this backend --packed is
NOT a real-model win; the engine targets the round-overhead portion and
the TPU fused path.

Sweeps sgd / momentum / adamw at several model sizes and T values.
Headline (the acceptance bar): sgd — the paper's local GD — on the
reduced paper-lenet config at T=16, packed ≥ 1.5x pytree steps/sec.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import warnings
from pathlib import Path

# the pytree round's int32 step counters can't always be aliased — noise
warnings.filterwarnings("ignore", message="Some donated buffers")

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:          # standalone invocation
    sys.path.insert(0, str(REPO_ROOT))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_trace, save_result
from repro import optim
from repro.configs.base import get_config
from repro.core import localsgd as lsgd
from repro.models import build_model
from repro.optim import packing

G = 4


def probe_loss(params, batch):
    """Separable quadratic: pulls every param toward the group target."""
    c = batch["c"]
    return sum(0.5 * jnp.sum(jnp.square(p.astype(jnp.float32) - c))
               for p in jax.tree.leaves(params)) * 1e-6


def _params_for(cfg):
    model = build_model(cfg, schedule="rect")
    return jax.tree.map(lambda s: jnp.full(s.shape, 0.1, s.dtype),
                        model.abstract())


class _Runner:
    """Holds one jitted variant's state so timing blocks of the variants
    can be interleaved (container timing drifts; interleaving keeps the
    comparison fair)."""

    def __init__(self, round_fn, state, batch):
        self.fn, self.state, self.batch = round_fn, state, batch
        self.times = []
        self.state = self.fn(self.state, self.batch)[0]   # compile + warm
        jax.block_until_ready(self.state)

    def run_block(self, reps):
        for _ in range(reps):
            t0 = time.time()
            self.state, _ = self.fn(self.state, self.batch)
            jax.block_until_ready(self.state)
            self.times.append(time.time() - t0)

    def median_s(self):
        return float(np.median(self.times))


def _bytes_accessed(fn, donate, *abstract_args):
    try:
        jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
        cost = jitted.lower(*abstract_args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        by = cost.get("bytes accessed")
        return None if by is None else float(by)
    except Exception:
        return None


def measure_pair(params, layout, loss_fn, opt_name, t_inner, batch_t,
                 batch_p, reps):
    """One (opt, T) cell: three engine variants.

      pytree       the seed round as shipped (per-step traj metrics)
      packed       the flat-buffer round, default contract (fused updates,
                   metrics evaluated once on the round's result)
      packed_traj  the flat-buffer round in metric-parity mode (per-step
                   trajectories like the seed) — isolates how much of the
                   win is fused updates vs the leaner metric contract
    """
    opt_t = optim.get(opt_name, 0.05)
    opt_p = optim.get(opt_name, 0.05, packed=True)
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner)
    lcfg_traj = dataclasses.replace(lcfg, metrics="traj")

    variants = {
        "pytree": (lsgd.make_local_round(loss_fn, opt_t, lcfg), opt_t,
                   None, batch_t),
        "packed": (lsgd.make_local_round(loss_fn, opt_p, lcfg,
                                         layout=layout), opt_p, layout,
                   batch_p),
        "packed_traj": (lsgd.make_local_round(loss_fn, opt_p, lcfg_traj,
                                              layout=layout), opt_p,
                        layout, batch_p),
    }
    runners = {}
    for vname, (rnd, opt, lay, batch) in variants.items():
        # every variant gets donated buffers: the comparison is engine vs
        # engine, not donation vs no-donation
        jitted = jax.jit(rnd, donate_argnums=(0,))
        state = lsgd.init_state(params, opt, n_groups=G, layout=lay)
        runners[vname] = _Runner(jitted, state, batch)
    block = max(2, reps // 3)
    done = 0
    while done < reps:                 # interleave the variants' timing
        for r in runners.values():
            r.run_block(min(block, reps - done))
        done += block

    out = {}
    for vname, (rnd, opt, lay, batch) in variants.items():
        sec = runners[vname].median_s()
        st_abs = jax.eval_shape(
            lambda o=opt, l=lay: lsgd.init_state(params, o, n_groups=G,
                                                 layout=l))
        out[vname] = {"round_s": sec, "steps_per_s": t_inner / sec,
                      "bytes_accessed": _bytes_accessed(rnd, True, st_abs,
                                                        batch)}
    out["speedup"] = out["pytree"]["round_s"] / out["packed"]["round_s"]
    out["speedup_traj_parity"] = (out["pytree"]["round_s"]
                                  / out["packed_traj"]["round_s"])
    by_t = out["pytree"]["bytes_accessed"]
    by_p = out["packed"]["bytes_accessed"]
    if by_t and by_p:
        out["bytes_moved_ratio"] = by_t / by_p
    return out


def _sharded_row(reps: int) -> dict:
    """Runs INSIDE the forced-8-device child (--sharded-child): the same
    packed T=16 sgd round on a (data=4, model=2) host mesh, executed two
    ways on the SAME padded ShardedLayout —

      replicated  buffer replicated within a group (the pre-shardexec
                  mesh path), GSPMD partitions the jnp fusion
      sharded     buffer split over "model", fused update + exchange in
                  shard_map blocks (DESIGN.md §9)

    Timed with impl="jnp" on both (the Pallas kernels only COMPILE on
    TPU; interpret mode would time the emulator, not the engine). The
    per-device state bytes are the memory headline: sharded cuts them by
    n_shards. Wall-clock on a host-platform CPU mesh mostly measures
    collective emulation — reported honestly, the win is the TPU path."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.sharding import shardexec as shx

    cfg = get_config("paper-lenet").reduced()
    params = _params_for(cfg)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                ("data", "model"))
    sexec = shx.plan_for(mesh)
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    t_inner = 16
    batch = {"c": jnp.linspace(0.0, 1.0, G)}
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner)
    out = {"mesh": [4, 2], "n_flat": layout.size,
           "n_flat_padded": layout.padded, "n_shards": sexec.n_shards,
           "T": t_inner, "opt": "sgd"}
    runners, per_dev = {}, {}
    for tag, sx in (("replicated", None), ("sharded", sexec)):
        opt = optim.get("sgd", 0.05, packed=True, impl="jnp")
        rnd = lsgd.make_local_round(probe_loss, opt, lcfg, layout=layout,
                                    shardexec=sx)
        spec = sexec.buf_spec() if sx is not None else P("data")
        buf_sh = NamedSharding(mesh, spec)
        rep_sh = NamedSharding(mesh, P())
        state = lsgd.init_state(params, opt, n_groups=G, layout=layout)
        state = jax.tree.map(
            lambda x: jax.device_put(
                x, buf_sh if (x.ndim == 2 and x.shape[-1] == layout.padded)
                else rep_sh), state)
        # per-device bytes of ONE (G, Np) state buffer under this
        # placement (sgd: just params; momentum/adamw moments scale the
        # same way) — the memory-scaling headline
        per_dev[tag] = int(np.prod(
            buf_sh.shard_shape((G, layout.padded)))) * 4
        runners[tag] = _Runner(jax.jit(rnd, donate_argnums=(0,)), state,
                               batch)
    block = max(2, reps // 3)
    done = 0
    while done < reps:
        for r in runners.values():
            r.run_block(min(block, reps - done))
        done += block
    for tag, r in runners.items():
        out[tag] = {"round_s": r.median_s(),
                    "steps_per_s": t_inner / r.median_s(),
                    "state_buf_bytes_per_device": per_dev[tag]}
    out["speedup_sharded_vs_replicated"] = (
        out["replicated"]["round_s"] / out["sharded"]["round_s"])
    out["per_device_state_reduction"] = (
        per_dev["replicated"] / per_dev["sharded"])
    return out


def _run_sharded_subprocess(reps: int) -> dict:
    """Fork a child with 8 forced host devices (the parent runs on the
    real single device; jax locks the count at init) and collect the
    sharded-vs-replicated row it prints as its last stdout line."""
    import subprocess

    from benchmarks.common import child_env

    r = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--sharded-child",
         str(reps)],
        env=child_env(force_devices=8), capture_output=True, text=True,
        timeout=1800)
    if r.returncode != 0:
        return {"error": (r.stderr or r.stdout)[-2000:]}
    return json.loads(r.stdout.strip().splitlines()[-1])


def _trace_overhead_row(reps: int, bar: float) -> dict:
    """Trace overhead (ISSUE 7 acceptance): the packed T=16 sgd headline
    round run two ways, interleaved —

      bare    fenced timing only (block_until_ready, no sink)
      traced  the full obs.Trace path every round: TraceAnnotation'd
              phase, fence, emit_round to a real JSONL sink

    throughput_ratio = bare_round_s / traced_round_s (1.0 == free). The
    bar gates via run.py --check: tracing must keep ≥ 95% of headline
    round throughput (85% in smoke — 3-rep medians on a noisy 2-core
    container)."""
    cfg = get_config("paper-lenet").reduced()
    params = _params_for(cfg)
    layout = packing.layout_of(params)
    t_inner = 16
    batch = {"c": jnp.linspace(0.0, 1.0, G)}
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner)
    opt = optim.get("sgd", 0.05, packed=True)
    rnd = jax.jit(lsgd.make_local_round(probe_loss, opt, lcfg,
                                        layout=layout), donate_argnums=(0,))
    wire = rnd.wire_bytes(lsgd.init_state(params, opt, n_groups=G,
                                          layout=layout))
    tr = bench_trace("trace_overhead",
                     meta={"config": cfg.name, "T": t_inner, "opt": "sgd"})

    class _TracedRunner(_Runner):
        n = 0

        def run_block(self, reps):
            for _ in range(reps):
                t0 = time.time()
                with tr.phase("round") as f:
                    self.state, m = f(self.fn(self.state, self.batch))
                tr.emit_round(_TracedRunner.n, {**m, **wire})
                _TracedRunner.n += 1
                self.times.append(time.time() - t0)

    runners = {}
    for tag, klass in (("bare", _Runner), ("traced", _TracedRunner)):
        state = lsgd.init_state(params, opt, n_groups=G, layout=layout)
        runners[tag] = klass(rnd, state, batch)
    block = max(2, reps // 3)
    done = 0
    while done < reps:
        for r in runners.values():
            r.run_block(min(block, reps - done))
        done += block
    tr.close()
    bare_s = runners["bare"].median_s()
    traced_s = runners["traced"].median_s()
    return {"config": cfg.name, "T": t_inner, "opt": "sgd",
            "bare_round_s": bare_s, "traced_round_s": traced_s,
            "trace_records": tr.n_records,
            "throughput_ratio": bare_s / traced_s, "bar": bar}


def _real_model_row(reps):
    """Supplementary: the same comparison with the REAL transformer loss
    (fwd/bwd dominates on CPU; expect ~1x — reported for honesty)."""
    cfg = get_config("paper-lenet").reduced()
    model = build_model(cfg, schedule="rect")
    params = model.init(jax.random.PRNGKey(0))
    layout = packing.layout_of(params)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (G, 1, 64)), jnp.int32)}
    return measure_pair(params, layout, model.loss, "sgd", 16,
                        batch, batch, max(2, reps // 2))


def main() -> dict:
    smoke = bool(int(os.environ.get("ROUND_THROUGHPUT_SMOKE", "0")))
    reps = 3 if smoke else 9

    lenet_red = get_config("paper-lenet").reduced()
    sizes = {
        "paper-lenet-reduced": lenet_red,
    }
    if not smoke:
        sizes["paper-lenet-reduced-d128"] = dataclasses.replace(
            lenet_red, name="paper-lenet-reduced-d128", d_model=128,
            d_ff=256, n_heads=4, n_kv_heads=2, head_dim=32)
        sizes["paper-lenet-reduced-d512"] = dataclasses.replace(
            lenet_red, name="paper-lenet-reduced-d512", d_model=512,
            d_ff=1024, n_heads=4, n_kv_heads=2, head_dim=128)
    t_values = [16] if smoke else [4, 16]
    opts = ["sgd"] if smoke else ["sgd", "momentum", "adamw"]

    batch = {"c": jnp.linspace(0.0, 1.0, G)}
    results = {}
    for cname, cfg in sizes.items():
        params = _params_for(cfg)
        layout = packing.layout_of(params)
        per_cfg = {"n_flat": layout.size, "n_leaves": len(layout.shapes),
                   "results": {}}
        for t_inner in t_values:
            for opt_name in opts:
                cell = measure_pair(params, layout, probe_loss, opt_name,
                                    t_inner, batch, batch, reps)
                per_cfg["results"][f"T{t_inner}/{opt_name}"] = cell
                print(f"  {cname} T={t_inner} {opt_name}: "
                      f"pytree {cell['pytree']['steps_per_s']:.1f} st/s, "
                      f"packed {cell['packed']['steps_per_s']:.1f} st/s "
                      f"({cell['speedup']:.2f}x; traj-parity "
                      f"{cell['speedup_traj_parity']:.2f}x)", flush=True)
        results[cname] = per_cfg

    head = results["paper-lenet-reduced"]["results"]["T16/sgd"]
    payload = {
        "G": G,
        "probe_loss": "separable quadratic (engine-isolating; see module "
                      "docstring)",
        "configs": results,
        "headline": {"config": "paper-lenet-reduced", "T": 16,
                     "opt": "sgd", "speedup": head["speedup"],
                     "bar": 1.5},
        "pass": head["speedup"] >= 1.5,
        "backend": jax.default_backend(),
        "smoke": smoke,
    }
    if not smoke:
        payload["real_model"] = _real_model_row(reps)
    # sharded-vs-replicated on a forced 8-device host mesh (DESIGN.md §9)
    # — runs in smoke too so CI exercises the shard_map wiring; a broken
    # child must FAIL the run, not record an error blob and stay green
    payload["sharded"] = _run_sharded_subprocess(max(3, reps // 2))
    # smoke runs get their own artifact so they never clobber the
    # committed full-run results (same rule as comm_bytes)
    artifact = "round_throughput_smoke" if smoke else "round_throughput"
    if "error" in payload["sharded"]:
        save_result(artifact, payload)
        raise SystemExit("sharded round-throughput child failed:\n"
                         + payload["sharded"]["error"])
    s = payload["sharded"]
    print(f"  sharded(4x2) T={s['T']} {s['opt']}: replicated "
          f"{s['replicated']['steps_per_s']:.1f} st/s, sharded "
          f"{s['sharded']['steps_per_s']:.1f} st/s "
          f"({s['speedup_sharded_vs_replicated']:.2f}x; state/device "
          f"1/{s['per_device_state_reduction']:.0f})", flush=True)
    # trace overhead on the same headline cell (ISSUE 7 acceptance:
    # per-round telemetry must keep >= 95% of bare round throughput)
    trow = _trace_overhead_row(reps, bar=0.85 if smoke else 0.95)
    payload["trace_overhead"] = trow
    payload["headline_trace"] = {
        "config": trow["config"], "T": trow["T"], "opt": trow["opt"],
        "throughput_ratio": trow["throughput_ratio"], "bar": trow["bar"]}
    payload["pass"] = bool(payload["pass"]
                           and trow["throughput_ratio"] >= trow["bar"])
    print(f"  trace overhead T={trow['T']} {trow['opt']}: bare "
          f"{trow['bare_round_s']*1e3:.1f}ms, traced "
          f"{trow['traced_round_s']*1e3:.1f}ms (throughput ratio "
          f"{trow['throughput_ratio']:.3f}, bar {trow['bar']})", flush=True)
    save_result(artifact, payload)
    if not smoke:
        # the committed perf-trajectory artifact — full runs only, so CI
        # smoke runs never clobber it with reduced data
        (REPO_ROOT / "BENCH_round_throughput.json").write_text(
            json.dumps(payload, indent=1, default=float))
    return payload


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded-child":
        reps_ = int(sys.argv[2]) if len(sys.argv) > 2 else 5
        print(json.dumps(_sharded_row(reps_), default=float))
        sys.exit(0)
    r = main()
    print(json.dumps(r["headline"], indent=1))
