"""Tiered fault domains: the hierarchical two-tier exchange priced and
stressed on its own links (ISSUE 10 / DESIGN.md §16).

Flat topologies treat every link the same; real clusters don't — the
intra-pod fabric (ICI) is fast and reliable, the cross-pod link (DCN) is
slow and lossy. The hierarchical exchange factors G = n_pods x pod_size,
runs an intra-pod consensus hop plus a cross-pod push-sum between pod
leaders, and carries an independent codec and an independent FaultPlan
per tier. Four sections price the claims:

  wire    the cross-tier codec: quantizing ONLY the DCN payload (int8
          inter codec) shrinks the cross-pod bytes ~3.9x while the
          intra-pod bytes stay untouched fp32 — per-tier accounting via
          ``wire_bytes_by_tier`` — plus an executed training sanity cell
          proving the quantized inter link still converges.
  sweep   hierarchical training cells through the packed round engine at
          0 / 7.5% DCN loss: the lossy cell must land within 10x of the
          lossless one (cross-tier push-sum conserves mass; loss only
          delays it).
  bias    the §16 design choice, mixing-only: at the SAME loss rate a
          flat masked-gossip hop drifts the group mean (consensus on a
          wrong point) while the tiered exchange's cross-pod push-sum
          ratio consensus stays unbiased to float32 resolution — the
          unbias factor is ~1e5 (bar 1e4).
  rejoin  graceful cross-tier degradation as exact booleans: a pod whose
          DCN uplink dies for a window degrades to local-only rounds
          (its pod mean frozen), total mass + queued backlog stays
          EXACTLY G every round, and after rejoin the drained backlog
          pulls every node to the true global mean.
  sharded (subprocess, 16 forced host devices: G=8 data shards x 2
          model shards — the tests/test_faults.py child pattern) the
          lossless-vs-lossy-DCN comparison re-run
          through the shard_map execution layer; tier masks are drawn
          outside shard_map, so the sharded cells replay the replicated
          schedule.

Headline (all bigger-is-better for run.py --check):

  cross_tier_wire_reduction  fp32 inter bytes / int8 inter bytes on the
                             same hierarchical exchange (>= 3.5).
  tier_unbias_factor         flat-gossip mean bias / tiered mean bias
                             under equal loss (>= 1e4).
  tier_gsq_margin            10x floored lossless gsq over the
                             7.5%-DCN-loss gsq (>= 1.0), replicated AND
                             sharded.

Writes experiments/bench/tier.json and the committed artifact
BENCH_tier.json on full runs. TIER_SMOKE=1 (or --smoke) runs the
reduced CI lane — fewer rounds, relaxed floors, still including the
forced-16-device sharded child — writing only tier_smoke.json. Exit
code reflects the pass flag.
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

G = 8
PODS = 4
D = 400
LR = 0.4
DCN_DROP = 0.075     # headline cross-pod loss rate (mid 5-10% band)
FAULT_SEED = 0       # training cells; the bias cell pins its own seed
BIAS_SEED = 2
GSQ_FLOOR = 1e-7             # converged-to-tolerance floor (full runs;
#                              G=8 fp32 rounds plateau at gsq ~1e-8)
GSQ_FLOOR_SMOKE = 1e-4
UNBIAS_BAR = 1e4
WIRE_BAR = 3.5


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


def hier(codec: str = "fp32", **kw):
    kw.setdefault("fault_seed", FAULT_SEED)
    return comm_mod.get_exchange("hierarchical", codec, G, n_pods=PODS,
                                 **kw)


def run_cell(params, batch, layout, ex, t_inner: int, rounds: int,
             shardexec=None) -> dict:
    """One hierarchical training cell through the packed round engine."""
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner)
    opt = optim.packed("sgd", LR, impl="jnp")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg,
                                        layout=layout, exchange=ex,
                                        shardexec=shardexec))
    state = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                            exchange=ex)
    m = None
    for _ in range(rounds):
        state, m = rnd(state, batch)
    by_tier = ex.wire_bytes_by_tier(layout.padded)
    wire = rnd.wire_bytes(state)["wire_bytes"]
    assert wire == by_tier["intra"] + by_tier["inter"], (wire, by_tier)
    return {
        "wire_bytes_per_round": wire,
        "wire_bytes_intra": int(by_tier["intra"]),
        "wire_bytes_inter": int(by_tier["inter"]),
        "delivery_rate_intra": ex.delivery_rate_intra,
        "delivery_rate_inter": ex.delivery_rate_inter,
        "participation_inter": float(m["participation_inter"]),
        "gsq_final": float(jnp.mean(m["grad_sq"])),
        "loss_final": float(jnp.mean(m["loss"])),
        "rounds": rounds, "comm": ex.name,
    }


def bias_cell(drop: float, iters: int = 60) -> dict:
    """Mixing-only consensus: flat gossip vs the tiered exchange under
    the same loss rate — where does each land relative to the true
    mean?"""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (G, 20)) * 3.0
    mean0 = np.asarray(jnp.mean(x, axis=0))
    cells = {
        "gossip_flat": comm_mod.get_exchange(
            "gossip", "fp32", G, mix_rounds=1, drop_rate=drop,
            fault_seed=BIAS_SEED),
        "hier_push_sum": hier(drop_rate=drop, fault_seed=BIAS_SEED),
    }
    out = {}
    for tag, ex in cells.items():
        st = ex.init(x)
        fn = jax.jit(ex.params)
        xs0 = x if ex.lossy_stream("params") else None
        y = x
        for _ in range(iters):
            y, st = fn(y, xs0, st)
        o = np.asarray(y)
        out[tag] = {
            "mean_bias": float(np.abs(o.mean(axis=0) - mean0).max()),
            "consensus_spread": float(np.abs(o - o.mean(axis=0)).max()),
            "iters": iters, "drop_rate": drop, "seed": BIAS_SEED,
            "comm": ex.name,
        }
    return out


def rejoin_cell(rounds: int = 24) -> dict:
    """Pod 1 (lanes 2-3) loses its DCN uplink for rounds [2, 5): exact
    degradation/rejoin booleans for the pass flag."""
    x = jax.random.normal(jax.random.PRNGKey(1), (G, 32))
    true_mean = np.asarray(x).mean(0)
    ex = hier(dropouts=((2, 2, 5), (3, 2, 5)), fault_seed=1)
    st = ex.init(x)
    fn = jax.jit(ex.params)
    y = x
    mass_ok, frozen_ok, pod1 = True, True, None
    for rnd in range(rounds):
        y, st = fn(y, None, st)
        mass = float(jnp.sum(st["mass"]) + jnp.sum(st["backlog_w"]))
        mass_ok = mass_ok and abs(mass - G) < 1e-3
        cur = np.asarray(y)[2:4].mean(0)
        if rnd == 2:
            pod1 = cur
        elif rnd in (3, 4):     # degraded: pod-local rounds only
            frozen_ok = frozen_ok and bool(
                np.allclose(cur, pod1, rtol=1e-5, atol=1e-6))
    final_bias = float(np.abs(np.asarray(y).mean(0) - true_mean).max())
    return {
        "mass_conserved_every_round": bool(mass_ok),
        "degraded_pod_mean_frozen": bool(frozen_ok),
        "rejoin_mean_bias": final_bias,
        "rejoin_exact": bool(mass_ok and frozen_ok and final_bias < 1e-3),
        "dropouts": [[2, 2, 5], [3, 2, 5]], "rounds": rounds,
    }


def _margin(gsq_lossless: float, gsq_faulty: float, floor: float) -> float:
    """>= 1.0 iff the lossy-DCN cell's gsq is within 10x of the lossless
    one, both floored at the convergence tolerance."""
    return 10.0 * max(gsq_lossless, floor) / max(gsq_faulty, floor)


# ---------------------------------------------------------------------------
# sharded child: the same comparison through the shard_map layer
# ---------------------------------------------------------------------------


def _child_main(rounds: int) -> dict:
    from jax.sharding import Mesh

    from repro.sharding import shardexec as shx

    out = {"n_devices": jax.device_count()}
    # groups map onto the data axis: G=8 data shards x 2 model shards
    mesh = Mesh(np.array(jax.devices()[:16]).reshape(8, 2),
                ("data", "model"))
    sexec = shx.plan_for(mesh)
    params, batch = make_feasibility()
    layout = packing.shard_layout(packing.layout_of(params),
                                  sexec.n_shards)
    for tag, kw in (("lossless", {}),
                    ("dcn_loss", dict(drop_rate=DCN_DROP))):
        out[tag] = run_cell(params, batch, layout, hier(**kw),
                            t_inner=16, rounds=rounds, shardexec=sexec)
    return out


def main() -> dict:
    smoke = bool(int(os.environ.get("TIER_SMOKE", "0"))) \
        or "--smoke" in sys.argv
    rounds = 15 if smoke else 120
    child_rounds = 15 if smoke else 120
    bias_iters = 30 if smoke else 60
    floor = GSQ_FLOOR_SMOKE if smoke else GSQ_FLOOR

    # -- wire: per-tier codec accounting + executed int8-inter sanity ----
    ex_f = hier(intra_topology="server", inter_topology="server")
    ex_q = hier(intra_topology="server", inter_topology="server",
                inter_codec="int8")
    bt_f = ex_f.wire_bytes_by_tier(D)
    bt_q = ex_q.wire_bytes_by_tier(D)
    wire_reduction = bt_f["inter"] / bt_q["inter"]
    assert bt_f["intra"] == bt_q["intra"], (bt_f, bt_q)  # intra untouched
    print(f"  wire: inter fp32 {bt_f['inter']:,}B -> int8 "
          f"{bt_q['inter']:,}B ({wire_reduction:.2f}x), intra "
          f"{bt_f['intra']:,}B both", flush=True)

    params, batch = make_feasibility()
    layout = packing.layout_of(params)
    sweep = {}
    for tag, ex in (
            ("lossless", hier()),
            ("dcn_loss", hier(drop_rate=DCN_DROP)),
            ("dcn_and_ici_loss", hier(drop_rate=DCN_DROP,
                                      intra_drop_rate=0.05)),
            ("int8_inter", ex_q)):
        cell = run_cell(params, batch, layout, ex, t_inner=16,
                        rounds=rounds)
        sweep[tag] = cell
        print(f"  {tag:17s} {cell['comm']:34s} "
              f"inter {cell['wire_bytes_inter']:>6,}B/round "
              f"gsq {cell['gsq_final']:.2e}", flush=True)
    margin = _margin(sweep["lossless"]["gsq_final"],
                     sweep["dcn_loss"]["gsq_final"], floor)

    bias = bias_cell(DCN_DROP, iters=bias_iters)
    unbias = (bias["gossip_flat"]["mean_bias"]
              / max(bias["hier_push_sum"]["mean_bias"], 1e-12))
    print(f"  bias@{DCN_DROP:g}: gossip "
          f"{bias['gossip_flat']['mean_bias']:.3f} tiered "
          f"{bias['hier_push_sum']['mean_bias']:.2e} "
          f"-> unbias factor {unbias:.0f}x", flush=True)

    rejoin = rejoin_cell()
    print(f"  rejoin: mass_conserved={rejoin['mass_conserved_every_round']}"
          f" frozen={rejoin['degraded_pod_mean_frozen']} "
          f"bias {rejoin['rejoin_mean_bias']:.1e}", flush=True)

    # -- forced-8-device shard_map path (same masks, same schedule) ------
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           str(child_rounds)]
    r = subprocess.run(cmd, env=child_env(16), capture_output=True,
                       text=True, timeout=1800, cwd=str(REPO_ROOT))
    if r.returncode != 0:
        sharded = {"error": (r.stderr or "")[-2000:]}
        sharded_margin = 0.0
    else:
        sharded = json.loads(r.stdout.strip().splitlines()[-1])
        sharded_margin = _margin(sharded["lossless"]["gsq_final"],
                                 sharded["dcn_loss"]["gsq_final"], floor)
        print(f"  sharded: lossless gsq "
              f"{sharded['lossless']['gsq_final']:.2e} dcn@{DCN_DROP:g} "
              f"{sharded['dcn_loss']['gsq_final']:.2e} "
              f"-> margin {sharded_margin:.1f}x", flush=True)

    payload = {
        "G": G, "n_pods": PODS, "dim": D, "lr": LR,
        "fault_seed": FAULT_SEED, "gsq_floor": floor,
        "problem": "consistent least squares over G nodes (Sec 2.3 "
                   "feasibility geometry)",
        "fault_model": "TieredFaultPlan: independent seed lanes per tier "
                       "(fault_seed_for), DCN loss on the inter tier "
                       "(DESIGN.md §16)",
        "wire": {"inter_fp32": int(bt_f["inter"]),
                 "inter_int8": int(bt_q["inter"]),
                 "intra_both": int(bt_f["intra"]),
                 "comm_fp32": ex_f.name, "comm_int8": ex_q.name},
        "sweep": sweep,
        "bias": bias,
        "rejoin": rejoin,
        "sharded": sharded,
        "headline": {
            "dcn_drop_rate": DCN_DROP, "T": 16,
            "cross_tier_wire_reduction": wire_reduction,
            "wire_bar": WIRE_BAR,
            "tier_unbias_factor": unbias, "unbias_bar": UNBIAS_BAR,
            "tier_gsq_margin": margin, "bar": 1.0,
            "lossless_gsq": sweep["lossless"]["gsq_final"],
            "dcn_loss_gsq": sweep["dcn_loss"]["gsq_final"],
            "gossip_bias": bias["gossip_flat"]["mean_bias"],
        },
        "headline_sharded": {
            "tier_gsq_margin": sharded_margin, "bar": 1.0,
        },
        "pass": bool(margin >= 1.0 and sharded_margin >= 1.0
                     and unbias >= UNBIAS_BAR
                     and wire_reduction >= WIRE_BAR
                     and rejoin["rejoin_exact"]
                     and sweep["lossless"]["gsq_final"] < floor
                     and sweep["int8_inter"]["gsq_final"] < floor),
        "backend": jax.default_backend(),
        "smoke": smoke,
    }
    save_result("tier_smoke" if smoke else "tier", payload)
    if not smoke:
        # the committed tiered-fault-domain artifact — full runs only
        (REPO_ROOT / "BENCH_tier.json").write_text(
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
