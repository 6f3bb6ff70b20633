"""Wire-byte frontier of the comm subsystem: T x codec x topology sweep.

The paper's claim is rounds-vs-bytes (arXiv:2102.01583 frames exactly this
resource); this benchmark prices it EXACTLY with the comm subsystem's
wire accounting (repro.comm, DESIGN.md §8) instead of post-hoc HLO
analysis. Two experiments, both through the packed round engine:

  sweep   convex feasibility (consistent least squares over G nodes,
          paper Sec 2.3 geometry) run to convergence for every
          (topology x codec x T) cell: exact payload bytes per round,
          cumulative bytes, and the final mean ||grad_i||^2 — showing
          the frontier (e.g. int8 cuts bytes ~3.9x at equal T with
          convergence preserved; delta coding makes quantization noise
          vanish as rounds converge).
  fig2    the paper's Fig-2(a) Beck-Teboulle feasibility re-run with the
          fp32 and int8 wire: the log-log slope of ||grad f(x_n)||^2 and
          the final residual must survive quantized communication.
  moments the multi-stream frontier (DESIGN.md §10): momentum/adamw x
          moment codec at T=16 with params pinned to int8 — for adamw
          the wire is DOMINATED by the two fp32 moment buffers, so the
          moment codec is the biggest remaining lever. Convergence bars:
          momentum must converge absolutely; adamw reaches its
          optimizer floor (~lr^2) and every lossy moment codec must
          match the moments-fp32 row within 2x.
  exchange_latency (embedded from benchmarks/exchange_latency.py,
          DESIGN.md §11): exact ppermute-vs-all_gather hop bytes, the
          fused-vs-staged epilogue timing, and — full runs — sharded
          top-k convergence + the fig2 suite under sharded top-k.

Headline (the acceptance bar): server topology, T=16 — int8 wire bytes
>= 3.5x under fp32 AND int8 converges to the same tolerance; fig2 keeps
slope < -0.5 and gsq_last < 1e-6 under int8; adamw params-int8 +
moments-int8 cuts >= 2.5x total wire vs params-int8/moments-fp32 with
convergence preserved; ring G=16 hop bytes cut >= 3x by the ppermute
neighbor exchange (exactly 7.5x) with sharded top-k matching replicated
top-k convergence and the fig2 slope (headline_exchange).

Writes experiments/bench/comm_bytes.json and the committed
perf-trajectory artifact BENCH_comm_bytes.json on full runs.
COMM_BYTES_SMOKE=1 runs a reduced sweep for CI with proportionally
relaxed convergence bars (so CI fails on real regressions, not just
crashes) and writes only comm_bytes_smoke.json — it never clobbers the
full-run artifacts. Exit code reflects the pass flag.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:          # standalone invocation
    sys.path.insert(0, str(REPO_ROOT))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_result
from repro import comm as comm_mod
from repro import optim
from repro.core import localsgd as lsgd
from repro.optim import packing

G = 4
D = 400          # model dim: int8 @ chunk=256 -> 4N/(N + 4*ceil(N/256))
LR = 0.4
GSQ_TOL = 1e-10  # converged: mean per-group ||grad_i||^2 at the result
# smoke runs use far fewer rounds, so the convergence bars scale with
# them — the CI step then FAILS (nonzero exit) on a real regression
# instead of only guarding against crashes
GSQ_TOL_SMOKE = 1e-5
FIG2_TOL, FIG2_TOL_SMOKE = 1e-6, 1e-4


def quad_loss(params, batch):
    r = batch["A"] @ params["w"] - batch["b"]
    return 0.5 * jnp.sum(r ** 2)


def make_feasibility(seed: int = 0, rows: int = 20):
    """Consistent least squares split over G nodes: every node's system
    is satisfiable at w*, so the intersection is non-empty and Alg 1
    converges (paper Sec 2.3 geometry)."""
    rng = np.random.RandomState(seed)
    A = rng.randn(G, rows, D).astype(np.float32) / np.sqrt(D)
    w_star = rng.randn(D).astype(np.float32)
    batch = {"A": jnp.asarray(A),
             "b": jnp.asarray(np.einsum("grd,d->gr", A, w_star))}
    params = {"w": jnp.asarray(rng.randn(D).astype(np.float32))}
    return params, batch


def run_cell(params, batch, layout, topology: str, codec: str, t_inner: int,
             rounds: int, gsq_tol: float = GSQ_TOL) -> dict:
    ex = comm_mod.get_exchange(topology, codec, G, staleness=1)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner)
    opt = optim.packed("sgd", LR, impl="jnp")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    state = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                            exchange=ex)
    m = None
    for _ in range(rounds):
        state, m = rnd(state, batch)
    wire = rnd.wire_bytes(state)["wire_bytes"]
    # the metric must agree with the exchange's static accounting
    assert wire == ex.wire_bytes_per_round(layout.size), (
        wire, ex.wire_bytes_per_round(layout.size))
    gsq = float(jnp.mean(m["grad_sq"]))
    return {
        "wire_bytes_per_round": wire,
        "cumulative_wire_mb": wire * rounds / 1e6,
        "gsq_final": gsq,
        "loss_final": float(jnp.mean(m["loss"])),
        "converged": bool(gsq < gsq_tol),
        "rounds": rounds,
    }


# ---------------------------------------------------------------------------
# Multi-stream sweep: momentum/adamw x moment codec (DESIGN.md §10)
# ---------------------------------------------------------------------------

# convergence: momentum reaches the feasibility point absolutely (like
# sgd); adamw's constant-lr steady state oscillates at ~lr^2, so its bar
# is the optimizer floor PLUS staying within 2x of its moments-fp32 row
MOMENT_OPTS = {"momentum": {"lr": 0.04, "rounds": 120, "tol": 1e-10},
               "adamw": {"lr": 0.02, "rounds": 400, "tol": 1e-2}}
MOMENT_OPTS_SMOKE = {"momentum": {"lr": 0.04, "rounds": 15, "tol": 1e-1},
                     "adamw": {"lr": 0.02, "rounds": 15, "tol": 1e0}}


def run_moment_cell(params, batch, layout, opt_name: str,
                    moment_codec: str, t_inner: int, lr: float,
                    rounds: int, tol: float) -> dict:
    """One cell of the moments frontier: params pinned to int8 (the §8
    result), moments through ``moment_codec`` — per-stream wire bytes
    from the round metrics, checked against the static accounting."""
    ex = comm_mod.get_exchange("server", "int8", G,
                               moment_codec=moment_codec)
    cfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner)
    opt = optim.packed(opt_name, lr, impl="jnp")
    rnd = jax.jit(lsgd.make_local_round(quad_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    state = lsgd.init_state(params, opt, n_groups=G, layout=layout,
                            exchange=ex)
    m = None
    for _ in range(rounds):
        state, m = rnd(state, batch)
    moment_sizes = {k: layout.padded for k in opt.moment_keys}
    by_stream = ex.wire_bytes_by_stream(layout.padded, moment_sizes)
    wb = rnd.wire_bytes(state)
    wire = wb["wire_bytes"]
    assert wire == sum(by_stream.values()), (wire, by_stream)
    for k, v in by_stream.items():
        assert wb[f"wire_bytes/{k}"] == v, (k, v)
    gsq = float(jnp.mean(m["grad_sq"]))
    return {
        "wire_bytes_per_round": wire,
        "wire_bytes_by_stream": by_stream,
        "moment_bytes_per_round": wire - by_stream["params"],
        "gsq_final": gsq,
        "loss_final": float(jnp.mean(m["loss"])),
        "converged": bool(gsq < tol),
        "rounds": rounds, "lr": lr,
    }


# ---------------------------------------------------------------------------
# Fig-2(a)-style check: Beck-Teboulle feasibility through the quantized wire
# ---------------------------------------------------------------------------


def bt_loss(params, batch):
    """The two Beck-Teboulle losses as ONE batch-indexed loss so the
    standard G-axis round runs them (group i gets batch["i"] == i)."""
    x, y = params["w"][0], params["w"][1]
    f1 = jnp.maximum(jnp.sqrt(x ** 2 + (y - 1.0) ** 2 + 1e-30) - 1.0,
                     0.0) ** 2
    f2 = jnp.maximum(y, 0.0) ** 2
    return jnp.where(batch["i"] == 0, f1, f2)


def run_fig2(codec: str, rounds: int, tol: float = FIG2_TOL) -> dict:
    m_nodes, T = 2, 10
    params = {"w": jnp.array([1.5, 0.8], jnp.float32)}
    layout = packing.layout_of(params)
    batch = {"i": jnp.arange(m_nodes)}
    ex = comm_mod.get_exchange("server", codec, m_nodes, chunk=256)
    cfg = lsgd.LocalSGDConfig(n_groups=m_nodes, inner_steps=T)
    opt = optim.packed("sgd", 0.4, impl="jnp")
    rnd = jax.jit(lsgd.make_local_round(bt_loss, opt, cfg, layout=layout,
                                        exchange=ex))
    state = lsgd.init_state(params, opt, n_groups=m_nodes, layout=layout,
                            exchange=ex)

    @jax.jit
    def global_gsq(w):   # ||grad of the AVERAGE objective||^2, as fig2a
        g = (jax.grad(lambda w: bt_loss({"w": w}, {"i": 0}))(w)
             + jax.grad(lambda w: bt_loss({"w": w}, {"i": 1}))(w)) / 2.0
        return jnp.sum(g ** 2)

    gsq, wire = [], 0
    for _ in range(rounds):
        state, m = rnd(state, batch)
        wire += rnd.wire_bytes(state)["wire_bytes"]
        gsq.append(float(global_gsq(state["params"][0])))
    n = np.arange(1, rounds + 1)
    tail = slice(rounds // 10, None)
    slope = float(np.polyfit(np.log(n[tail]),
                             np.log(np.maximum(gsq, 1e-300))[tail], 1)[0])
    return {"codec": codec, "rounds": rounds, "T": T,
            "wire_bytes_total": wire,
            "gsq_first": gsq[0], "gsq_last": gsq[-1],
            "loglog_slope": slope,
            "pass": bool(slope < -0.5 and gsq[-1] < tol)}


def main() -> dict:
    smoke = bool(int(os.environ.get("COMM_BYTES_SMOKE", "0")))
    rounds = 15 if smoke else 120
    fig2_rounds = 150 if smoke else 2000
    gsq_tol = GSQ_TOL_SMOKE if smoke else GSQ_TOL
    fig2_tol = FIG2_TOL_SMOKE if smoke else FIG2_TOL
    topologies = ["server", "ring"] if smoke else \
        ["server", "ring", "gossip", "async_stale", "none"]
    codecs = ["fp32", "int8"] if smoke else \
        ["fp32", "fp16", "bf16", "int8", "topk"]
    t_values = [16] if smoke else [4, 16]

    params, batch = make_feasibility()
    layout = packing.layout_of(params)
    sweep = {}
    for topo in topologies:
        for codec in codecs:
            if topo == "async_stale" and codec == "topk":
                continue   # refused: staleness drops rounds, EF assumes
                           # delivery (DESIGN.md §8)
            if topo == "none" and codec != "fp32":
                continue   # no wire -> codecs are skipped entirely; one
                           # baseline row is enough
            for t in t_values:
                cell = run_cell(params, batch, layout, topo, codec, t,
                                rounds, gsq_tol=gsq_tol)
                sweep[f"{topo}/{codec}/T{t}"] = cell
                print(f"  {topo:11s} {codec:5s} T={t:<3d} "
                      f"wire {cell['wire_bytes_per_round']:>6,}B/round "
                      f"gsq {cell['gsq_final']:.2e} "
                      f"{'ok' if cell['converged'] else '--'}", flush=True)

    t_head = t_values[-1]
    fp32 = sweep[f"server/fp32/T{t_head}"]
    i8 = sweep[f"server/int8/T{t_head}"]
    reduction = fp32["wire_bytes_per_round"] / i8["wire_bytes_per_round"]
    fig2 = {c: run_fig2(c, fig2_rounds, tol=fig2_tol)
            for c in ("fp32", "int8")}
    for c, r in fig2.items():
        print(f"  fig2 {c}: slope {r['loglog_slope']:.2f} "
              f"gsq_last {r['gsq_last']:.2e} "
              f"{'ok' if r['pass'] else '--'}", flush=True)

    # ---- multi-stream frontier: moment codecs (DESIGN.md §10) ----------
    mopts = MOMENT_OPTS_SMOKE if smoke else MOMENT_OPTS
    mcodecs = ["fp32", "int8"] if smoke else ["fp32", "bf16", "int8"]
    moments = {}
    for opt_name, hp in mopts.items():
        for mc in mcodecs:
            cell = run_moment_cell(params, batch, layout, opt_name, mc,
                                   t_head, hp["lr"], hp["rounds"],
                                   hp["tol"])
            moments[f"server/{opt_name}/params-int8/moments-{mc}"] = cell
            print(f"  {opt_name:9s} moments={mc:5s} T={t_head:<3d} "
                  f"wire {cell['wire_bytes_per_round']:>6,}B/round "
                  f"(moments {cell['moment_bytes_per_round']:>6,}B) "
                  f"gsq {cell['gsq_final']:.2e} "
                  f"{'ok' if cell['converged'] else '--'}", flush=True)
    # ---- exchange engine: hop bytes + fused epilogue (DESIGN.md §11) ---
    from benchmarks import exchange_latency
    exch = exchange_latency.run(smoke=smoke)
    print(f"  exchange: ring G=16 hop bytes "
          f"{exch['headline']['ring_hop_bytes_reduction_G16']:.1f}x "
          f"under all_gather (bar {exch['headline']['bar']}); fused "
          f"epilogue server/int8 "
          f"{exch['headline']['fused_epilogue_speedup_server_int8']:.2f}x"
          f" {'ok' if exch['pass'] else '--'}", flush=True)

    a_fp32 = moments["server/adamw/params-int8/moments-fp32"]
    a_i8 = moments["server/adamw/params-int8/moments-int8"]
    moment_reduction = (a_fp32["wire_bytes_per_round"]
                        / a_i8["wire_bytes_per_round"])
    # EVERY swept moment cell must converge (momentum absolutely, adamw
    # to its optimizer floor), and every lossy adamw row — bf16 included
    # — must match the moments-fp32 floor within 2x
    moments_ok = bool(
        all(c["converged"] for c in moments.values())
        and all(moments[f"server/adamw/params-int8/moments-{mc}"]
                ["gsq_final"] <= 2.0 * max(a_fp32["gsq_final"], 1e-12)
                for mc in mcodecs if mc != "fp32"))

    payload = {
        "G": G, "dim": D, "lr": LR, "gsq_tol": gsq_tol,
        "problem": "consistent least squares over G nodes (Sec 2.3 "
                   "feasibility geometry); fig2 = Beck-Teboulle, T=10",
        "accounting": "exact per-stream payload bytes, up+down totals "
                      "(Exchange.wire_bytes_by_stream, DESIGN.md §8/§10)",
        "sweep": sweep,
        "fig2": fig2,
        "moments": moments,
        "headline": {
            "topology": "server", "T": t_head,
            "int8_reduction_vs_fp32": reduction, "bar": 3.5,
            "fp32_gsq": fp32["gsq_final"], "int8_gsq": i8["gsq_final"],
        },
        "headline_moments": {
            "topology": "server", "T": t_head, "opt": "adamw",
            "int8_moments_reduction_vs_fp32_moments": moment_reduction,
            "bar": 2.5,
            "fp32_moments_gsq": a_fp32["gsq_final"],
            "int8_moments_gsq": a_i8["gsq_final"],
        },
        "exchange_latency": exch,
        "headline_exchange": exch["headline"],
        "pass": bool(reduction >= 3.5 and fp32["converged"]
                     and i8["converged"] and fig2["int8"]["pass"]
                     and moment_reduction >= 2.5 and moments_ok
                     and exch["pass"]),
        "backend": jax.default_backend(),
        "smoke": smoke,
    }
    # smoke runs get their own artifact so they never clobber the
    # committed full-run results under experiments/bench/
    save_result("comm_bytes_smoke" if smoke else "comm_bytes", payload)
    if not smoke:
        # the committed wire-byte-frontier artifact — full runs only
        (REPO_ROOT / "BENCH_comm_bytes.json").write_text(
            json.dumps(payload, indent=1, default=float))
    return payload


if __name__ == "__main__":
    r = main()
    print(json.dumps(r["headline"], indent=1))
    sys.exit(0 if r["pass"] else 1)
