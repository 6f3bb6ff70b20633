"""Fault-tolerance frontier: drop rate x topology x T (ISSUE 6 /
DESIGN.md §12).

The paper's convergence claims assume a reliable network; this benchmark
prices what packet loss does to each exchange topology with the
DETERMINISTIC FaultPlan masks (seeded, replayable — every cell is a pure
function of its config). Three sections:

  sweep   the convex feasibility problem (consistent least squares over
          G nodes, Sec 2.3 geometry) for every (topology x drop_rate x
          T) cell through the packed round engine: final mean
          ||grad_i||^2, delivered-fraction participation, and the
          exchange's own wire accounting (push_sum prices only
          DELIVERED edges; server/ring price attempts).
  bias    the mixing-only consensus experiment behind the §12 design
          choice: under 5% drop the masked doubly-stochastic hop
          (gossip) contracts the spread but DRIFTS the group mean —
          consensus on a provably wrong point — while push-sum ratio
          consensus under the SAME masks stays unbiased (mass is
          conserved, loss only delays it).
  sharded (subprocess with 8 forced host devices, the same pattern as
          tests/test_faults.py's REPRO_SHARDEXEC_CHILD driver) the
          push_sum-vs-lossless comparison re-run through the shard_map
          execution layer — the fault masks are generated outside the
          shard_map block, so the sharded cells replay the replicated
          schedule.

Headline (the acceptance bars, all bigger-is-better for run.py --check):

  push_sum_gsq_margin    10x tolerance-floored lossless gsq over the
                         push_sum-at-5%-drop gsq (>= 1.0 means push_sum
                         converges within 10x of lossless fp32), on the
                         replicated AND the sharded path.
  push_sum_unbias_factor gossip mixing bias / push_sum mixing bias under
                         the same 5% masks (>= 100).

Writes experiments/bench/fault_tolerance.json and the committed
perf-trajectory artifact BENCH_fault.json on full runs. FAULT_SMOKE=1
(or --smoke) runs the reduced CI lane — fewer rounds/cells but still
including the forced-8-device sharded child — with proportionally
relaxed convergence floors, writing only fault_tolerance_smoke.json.
Exit code reflects the pass flag.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:          # standalone invocation
    sys.path.insert(0, str(REPO_ROOT))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import child_env, save_result
from repro import comm as comm_mod
from repro import optim
from repro.core import localsgd as lsgd
from repro.optim import packing

G = 4
D = 400
LR = 0.4
FAULT_SEED = 0       # training cells; the bias cell pins its own seed
BIAS_SEED = 2        # an early-loss schedule: the drift is unmistakable
GSQ_FLOOR = 1e-10            # converged-to-tolerance floor (full runs)
GSQ_FLOOR_SMOKE = 1e-4
UNBIAS_BAR = 100.0


def quad_loss(params, batch):
    r = batch["A"] @ params["w"] - batch["b"]
    return 0.5 * jnp.sum(r ** 2)


def make_feasibility(seed: int = 0, rows: int = 20):
    rng = np.random.RandomState(seed)
    A = rng.randn(G, rows, D).astype(np.float32) / np.sqrt(D)
    w_star = rng.randn(D).astype(np.float32)
    batch = {"A": jnp.asarray(A),
             "b": jnp.asarray(np.einsum("grd,d->gr", A, w_star))}
    params = {"w": jnp.asarray(rng.randn(D).astype(np.float32))}
    return params, batch


def run_cell(params, batch, layout, topology: str, drop: float,
             t_inner: int, rounds: int, shardexec=None) -> dict:
    """One (topology x drop x T) training cell through the packed round
    (fp32 wire; the codec frontier is BENCH_comm_bytes.json's job)."""
    ex = comm_mod.get_exchange(topology, "fp32", G, drop_rate=drop,
                               fault_seed=FAULT_SEED)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner)
    opt = optim.packed("sgd", LR, impl="jnp")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex,
                                        shardexec=shardexec))
    state = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                            exchange=ex)
    parts = []
    m = None
    for _ in range(rounds):
        state, m = rnd(state, batch)
        if "participation" in m:
            parts.append(float(m["participation"]))
    wire = rnd.wire_bytes(state)["wire_bytes"]
    # sharded layouts pad the buffer to the shard grid; the round prices
    # the actual (padded) payload it ships
    assert wire == ex.wire_bytes_per_round(layout.padded), (
        wire, ex.wire_bytes_per_round(layout.padded))
    return {
        "wire_bytes_per_round": wire,
        "delivery_rate": ex.delivery_rate,
        "participation_mean": float(np.mean(parts)) if parts else 1.0,
        "gsq_final": float(jnp.mean(m["grad_sq"])),
        "loss_final": float(jnp.mean(m["loss"])),
        "rounds": rounds,
    }


def bias_cell(drop: float, iters: int = 60) -> dict:
    """Mixing-only consensus under identical fault masks: iterate the
    exchange as a pure consensus map and measure where it lands."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (G, 20)) * 3.0
    mean0 = np.asarray(jnp.mean(x, axis=0))
    out = {}
    for topology in ("gossip", "push_sum"):
        ex = comm_mod.get_exchange(topology, "fp32", G, mix_rounds=1,
                                   drop_rate=drop, fault_seed=BIAS_SEED)
        st = ex.init(x)
        fn = jax.jit(ex.params)
        y = x
        for _ in range(iters):
            y, st = fn(y, None, st)
        o = np.asarray(y)
        out[topology] = {
            "mean_bias": float(np.abs(o.mean(axis=0) - mean0).max()),
            "consensus_spread": float(np.abs(o - o.mean(axis=0)).max()),
            "iters": iters, "drop_rate": drop, "seed": BIAS_SEED,
        }
    return out


def _margin(gsq_lossless: float, gsq_faulty: float, floor: float) -> float:
    """>= 1.0 iff the faulty cell's gsq is within 10x of the lossless
    one, both floored at the convergence tolerance (two runs at the
    numerical floor should PASS, not divide noise by noise)."""
    return 10.0 * max(gsq_lossless, floor) / max(gsq_faulty, floor)


# ---------------------------------------------------------------------------
# sharded child: the same comparison through the shard_map layer
# ---------------------------------------------------------------------------


def _child_main(rounds: int) -> dict:
    from jax.sharding import Mesh

    from repro.sharding import shardexec as shx

    out = {"n_devices": jax.device_count()}
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                ("data", "model"))
    sexec = shx.plan_for(mesh)
    params, batch = make_feasibility()
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    for tag, topology, drop in (("lossless", "server", 0.0),
                                ("push_sum_5pct", "push_sum", 0.05),
                                ("push_sum_10pct", "push_sum", 0.10)):
        out[tag] = run_cell(params, batch, layout, topology, drop,
                            t_inner=16, rounds=rounds, shardexec=sexec)
    return out


def main() -> dict:
    smoke = bool(int(os.environ.get("FAULT_SMOKE", "0"))) \
        or "--smoke" in sys.argv
    rounds = 15 if smoke else 120
    child_rounds = 15 if smoke else 120
    floor = GSQ_FLOOR_SMOKE if smoke else GSQ_FLOOR
    topologies = ["server", "gossip", "push_sum"] if smoke else \
        ["server", "ring", "gossip", "push_sum"]
    drops = [0.0, 0.05] if smoke else [0.0, 0.05, 0.10]
    t_values = [16] if smoke else [4, 16]

    params, batch = make_feasibility()
    layout = packing.layout_of(params)
    sweep = {}
    for topo in topologies:
        for drop in drops:
            for t in t_values:
                cell = run_cell(params, batch, layout, topo, drop, t,
                                rounds)
                sweep[f"{topo}/drop{drop:g}/T{t}"] = cell
                print(f"  {topo:9s} drop={drop:<5g} T={t:<3d} "
                      f"wire {cell['wire_bytes_per_round']:>6,}B/round "
                      f"part {cell['participation_mean']:.2f} "
                      f"gsq {cell['gsq_final']:.2e}", flush=True)

    t_head = t_values[-1]
    lossless = sweep[f"server/drop0/T{t_head}"]
    ps5 = sweep[f"push_sum/drop0.05/T{t_head}"]
    margin = _margin(lossless["gsq_final"], ps5["gsq_final"], floor)

    bias = bias_cell(0.05)
    unbias = (bias["gossip"]["mean_bias"]
              / max(bias["push_sum"]["mean_bias"], 1e-12))
    print(f"  bias@5%: gossip {bias['gossip']['mean_bias']:.3f} "
          f"(spread {bias['gossip']['consensus_spread']:.1e}) "
          f"push_sum {bias['push_sum']['mean_bias']:.2e} "
          f"-> unbias factor {unbias:.0f}x", flush=True)

    # -- forced-8-device shard_map path (same masks, same schedule) ------
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           str(child_rounds)]
    r = subprocess.run(cmd, env=child_env(8), capture_output=True,
                       text=True, timeout=1800, cwd=str(REPO_ROOT))
    if r.returncode != 0:
        sharded = {"error": (r.stderr or "")[-2000:]}
        sharded_margin = 0.0
    else:
        sharded = json.loads(r.stdout.strip().splitlines()[-1])
        sharded_margin = _margin(sharded["lossless"]["gsq_final"],
                                 sharded["push_sum_5pct"]["gsq_final"],
                                 floor)
        print(f"  sharded: lossless gsq "
              f"{sharded['lossless']['gsq_final']:.2e} push_sum@5% "
              f"{sharded['push_sum_5pct']['gsq_final']:.2e} "
              f"-> margin {sharded_margin:.1f}x", flush=True)

    payload = {
        "G": G, "dim": D, "lr": LR, "fault_seed": FAULT_SEED,
        "gsq_floor": floor,
        "problem": "consistent least squares over G nodes (Sec 2.3 "
                   "feasibility geometry), fp32 wire",
        "fault_model": "deterministic FaultPlan masks, pure in (round, "
                       "seed): Bernoulli per-edge drops (DESIGN.md §12)",
        "sweep": sweep,
        "bias": bias,
        "sharded": sharded,
        "headline": {
            "topology": "push_sum", "T": t_head, "drop_rate": 0.05,
            "push_sum_gsq_margin": margin, "bar": 1.0,
            "push_sum_unbias_factor": unbias, "unbias_bar": UNBIAS_BAR,
            "lossless_gsq": lossless["gsq_final"],
            "push_sum_gsq": ps5["gsq_final"],
            "gossip_bias_at_5pct": bias["gossip"]["mean_bias"],
        },
        "headline_sharded": {
            "push_sum_gsq_margin": sharded_margin, "bar": 1.0,
        },
        "pass": bool(margin >= 1.0 and sharded_margin >= 1.0
                     and unbias >= UNBIAS_BAR
                     and lossless["gsq_final"] < floor
                     and sweep[f"push_sum/drop0/T{t_head}"]["gsq_final"]
                     < floor),
        "backend": jax.default_backend(),
        "smoke": smoke,
    }
    save_result("fault_tolerance_smoke" if smoke else "fault_tolerance",
                payload)
    if not smoke:
        # the committed fault-tolerance artifact — full runs only
        (REPO_ROOT / "BENCH_fault.json").write_text(
            json.dumps(payload, indent=1, default=float))
    return payload


if __name__ == "__main__":
    if "--child" in sys.argv:
        n = int(sys.argv[sys.argv.index("--child") + 1])
        print(json.dumps(_child_main(rounds=n), default=float))
        sys.exit(0)
    res = main()
    print(json.dumps(res["headline"], indent=1))
    sys.exit(0 if res["pass"] else 1)
