"""The paper's contribution as a composable JAX module (Alg 1).

Model-averaging distributed optimization:

    worker i:  pull x_n; run T_i local GD steps (or until ||grad||^2 <= eps,
               the paper's "Threshold" / T_i = infinity mode); push result
    server:    x_{n+1} = (1/m) sum_i x_n^{i,T_i}

SPMD mapping (see DESIGN.md): every state leaf carries a leading group axis
G sharded over the ("pod","data") mesh axes. Local steps are vmapped over G
— zero cross-group collectives. The per-round model exchange is the ONLY
cross-pod/data communication; it is routed through the pluggable
``repro.comm.Exchange`` layer (DESIGN.md §8) — topology x codec + exact
wire-byte accounting — and defaults to server/fp32, which is bit-exact
with the original ``average_groups`` (mean over G + broadcast): one
all-reduce of the model per round, instead of one gradient all-reduce per
step (the conventional baseline, also provided here as ``make_sync_step``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import comm as comm_mod
from repro.optim import Optimizer, map_moments, packing


@dataclasses.dataclass(frozen=True)
class LocalSGDConfig:
    n_groups: int                 # m in the paper
    inner_steps: int = 1          # T (uniform), or max T when t_i is set
    # Per-node T_i (paper Alg 1 allows a different count per worker i).
    # Tuple of length n_groups; each group runs its own T_i <= inner_steps
    # (implemented as a masked scan to the max — SPMD-friendly).
    t_i: Optional[Tuple[int, ...]] = None
    threshold: Optional[float] = None  # if set: T_i = inf mode, stop at
                                       # ||grad_i||^2 <= threshold
    max_inner: int = 1_000        # hard cap for threshold mode
    inner_mode: str = "fixed_batch"    # fixed_batch (paper GD) | microbatch
    average_opt_state: bool = True
    # Metric granularity of the PACKED round (DESIGN.md §6): "final"
    # evaluates loss/||grad||^2 once at the round's result (the fixed-T
    # algorithm needs no per-step diagnostics — materializing them costs
    # ~2 extra passes over the model per inner step); "traj" matches the
    # pytree round's per-step trajectories (needed by the Sec-4 adaptive-T
    # controller). The pytree round always records trajectories.
    metrics: str = "final"


class TrainState(dict):
    """{"params": pytree, "opt": pytree} — plain dict for pytree-ness."""


def replicate(tree, n_groups: int):
    """Tile a pytree with a leading group axis (all groups identical)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), tree)


def average_groups(tree):
    """Model averaging: mean over the leading G axis, broadcast back.

    This is the paper's server combination step — kept as the reference
    the ``comm.Exchange`` server backend must stay bit-exact with (the
    rounds themselves route through the exchange; see DESIGN.md §8).
    """
    def avg(x):
        m = jnp.mean(x, axis=0, keepdims=True)
        return jnp.broadcast_to(m, x.shape)

    return jax.tree.map(avg, tree)


def _resolve_exchange(exchange, cfg: LocalSGDConfig, layout):
    """Default + validate the round's exchange (see DESIGN.md §8/§10 for
    the combinations that refuse)."""
    exch = exchange if exchange is not None else comm_mod.default_exchange(
        cfg.n_groups)
    if exch.n_groups != cfg.n_groups:
        raise ValueError(f"exchange built for G={exch.n_groups} but "
                         f"cfg.n_groups={cfg.n_groups}")
    if exch.codec.flat_only and layout is None and exch.topology != "none":
        # ("none" is exempt: nothing goes on the wire, the codec never runs)
        raise NotImplementedError(
            f"codec {exch.codec.name!r} needs the packed (G, N) buffer as "
            "its wire format — run the round with a packing.Layout "
            "(DESIGN.md §8)")
    if (cfg.average_opt_state and exch.mcodec.flat_only and layout is None
            and exch.topology != "none"):
        raise NotImplementedError(
            f"moment codec {exch.mcodec.name!r} needs packed flat moment "
            "buffers as its wire format — run the round with a "
            "packing.Layout and a packed optimizer (DESIGN.md §10)")
    if (exch.downlink_codec is not None and exch.downlink_codec.flat_only
            and layout is None and exch.topology != "none"):
        raise NotImplementedError(
            f"downlink codec {exch.downlink_codec.name!r} needs the "
            "packed flat buffer as its wire format — run the round with "
            "a packing.Layout (DESIGN.md §11)")
    if cfg.average_opt_state and not exch.supports_opt_state_averaging:
        raise NotImplementedError(
            f"{exch.topology} cannot average opt state; set "
            "average_opt_state=False (DESIGN.md §10)")
    if exch.overlap and layout is None:
        raise NotImplementedError(
            "the overlapped (delayed-mixing) exchange double-buffers the "
            "packed flat stream payload as comm['inflight'] — run the "
            "round with a packing.Layout and a packed optimizer "
            "(DESIGN.md §14); the pytree path has no single donation-"
            "safe buffer to put in flight")
    return exch


def _check_comm_state(exch, state_G, mkeys=()):
    if exch.stateful and "comm" not in state_G:
        raise ValueError(
            f"exchange {exch.name!r} carries round-to-round state "
            "(staleness buffers / codec residuals); build the train state "
            "with init_state(..., exchange=...)")
    if (exch.topology == "async_stale" and mkeys
            and "pushed_opt" not in state_G.get("comm", {})):
        raise ValueError(
            "async_stale averages opt state through per-stream staleness "
            "buffers; build the train state with init_state(..., "
            "exchange=...) so comm['pushed_opt'] is allocated "
            "(DESIGN.md §10)")
    if (exch.topology == "push_sum"
            and "mass" not in state_G.get("comm", {})):
        raise ValueError(
            "push_sum is ratio consensus: every round needs the mass "
            "counters and per-edge backlog buffers; build the train state "
            "with init_state(..., exchange=...) so comm['mass'] / "
            "comm['backlog'] are allocated (DESIGN.md §12)")
    if (exch.faulty and exch.topology == "server"
            and "pushed" not in state_G.get("comm", {})):
        raise ValueError(
            "a faulty server exchange retries dropped pushes from "
            "per-group staleness buffers; build the train state with "
            "init_state(..., exchange=...) so comm['pushed'] is "
            "allocated (DESIGN.md §12)")
    if (exch.hierarchical and exch.inter_topology == "push_sum"
            and exch.n_pods > 1
            and "mass" not in state_G.get("comm", {})):
        raise ValueError(
            "hierarchical push_sum inter tier is ratio consensus: every "
            "round needs the pod-level mass counters and per-edge "
            "backlogs; build the train state with init_state(..., "
            "exchange=...) so comm['mass'] / comm['backlog'] are "
            "allocated (DESIGN.md §16)")
    if exch.overlap and "inflight" not in state_G.get("comm", {}):
        raise ValueError(
            "an overlapped exchange double-buffers the previous round's "
            "payload; build the train state with init_state(..., "
            "exchange=...) so comm['inflight'] is allocated "
            "(DESIGN.md §14)")


def round_wire_bytes(exch, params_G, opt_G, avg_opt: bool,
                     n_groups: int) -> dict:
    """Exact payload bytes one round puts on the wire (Python ints from
    shapes only — arrays or ``ShapeDtypeStruct``s), matching what the
    round actually exchanges: every
    stream of the payload through ITS codec — params via the params
    codec, each moment stream via the moment codec (DESIGN.md §10). The
    step counter is never exchanged on either path. Returns the totals
    (``wire_bytes`` — the physical total, p2p payloads count once —
    plus per-direction ``wire_bytes_up`` / ``wire_bytes_down``) and one
    ``wire_bytes/<stream>`` key per stream; the totals are exactly the
    sums of the per-stream splits.

    The counts stay on the host: a jitted output would carry them as
    int32, which wraps above 2 GiB of wire per round (a full-width model
    at G=4 is past that). Each round function exposes them as
    ``round_.wire_bytes(state_G)``; the caller merges them into the round
    record (DESIGN.md §13)."""
    n = sum(l.size // n_groups for l in jax.tree.leaves(params_G))
    moment_sizes = {}
    if avg_opt:
        moment_sizes = {
            k: sum(l.size // n_groups for l in jax.tree.leaves(v))
            for k, v in opt_G.items() if k != "count"}
    by_stream = exch.wire_bytes_by_stream(n, moment_sizes)
    by_tier = exch.wire_bytes_by_tier(n, moment_sizes)
    out = {"wire_bytes": sum(by_stream.values()),
           "wire_bytes_up": exch.wire_bytes_up(n, moment_sizes=moment_sizes),
           "wire_bytes_down": exch.wire_bytes_down(
               n, moment_sizes=moment_sizes),
           # per-tier totals (DESIGN.md §16): flat topologies put the
           # whole wire on the intra tier (one big pod), inter = 0
           "wire_bytes_intra": by_tier["intra"],
           "wire_bytes_inter": by_tier["inter"]}
    out.update({f"wire_bytes/{k}": v for k, v in by_stream.items()})
    return out


def _with_wire_bytes(round_, exch, cfg: LocalSGDConfig):
    """Attach ``round_.wire_bytes(state_G)``: the host-side exact wire
    counts of one round on that state (see ``round_wire_bytes``).
    ``jax.jit`` copies the attribute onto the jitted round."""
    round_.wire_bytes = lambda state_G: round_wire_bytes(
        exch, state_G["params"], state_G["opt"], cfg.average_opt_state,
        cfg.n_groups)
    return round_


def _clamp_nonneg_streams(mixed: dict, opt, exch) -> dict:
    """Project lossy-decoded non-negative moment streams (adamw's second
    moment) back onto [0, inf): a delta codec's decode error is bounded
    by the chunk scale, so small-magnitude v elements can come back
    slightly negative and sqrt(v) would NaN. The true value is >= 0, so
    the projection only shrinks the decode error. Identity moment codecs
    skip this entirely (the default path stays bit-exact). Overlap mode
    always projects: the delayed-mixing correction is ADDITIVE
    (``v_T + mix(inflight) - inflight``), so even an fp32 payload can
    push a near-zero v element negative (DESIGN.md §14)."""
    if ((exch.mcodec.identity and not exch.lossy_downlink
         and not exch.overlap) or exch.topology == "none"):
        return mixed
    nonneg = getattr(opt, "moment_nonneg", ())
    return {k: (jax.tree.map(lambda x: jnp.maximum(x, 0.0), v)
                if k in nonneg else v)
            for k, v in mixed.items()}


def grad_sq_norm(grads, use_pallas: bool = False) -> jax.Array:
    """||g||^2. On a packed flat buffer this is ONE fused reduction
    (optionally the Pallas sq_norm kernel) instead of one partial sum
    per pytree leaf."""
    if isinstance(grads, jax.Array):
        if use_pallas:
            from repro.kernels import use_interpret
            from repro.kernels.sq_norm import sq_norm
            return sq_norm(grads.reshape(-1), interpret=use_interpret())
        return jnp.sum(jnp.square(grads.astype(jnp.float32)))
    return sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
               for g in jax.tree.leaves(grads))


def _grad_sq_norm_groups(grads_G, use_pallas: bool = False) -> jax.Array:
    """Per-group ||g||^2 for a (G, N) packed gradient buffer -> (G,)."""
    if use_pallas:
        from repro.kernels import use_interpret
        from repro.kernels.sq_norm import sq_norm_groups
        return sq_norm_groups(grads_G, interpret=use_interpret())
    return jnp.sum(jnp.square(grads_G.astype(jnp.float32)), axis=-1)


# ---------------------------------------------------------------------------
# Uniform round observability block (DESIGN.md §13)
# ---------------------------------------------------------------------------


def _consensus_sq_flat(x_G, use_pallas: bool = False) -> jax.Array:
    """Per-group consensus distance ||x_g - x̄||² of a (G, N) buffer ->
    (G,): the pad region is zero in every group, so it contributes
    nothing. The deviation is formed in fp32 and reduced by the same
    sq_norm path the grad metrics use."""
    x32 = x_G.astype(jnp.float32)
    d = x32 - jnp.mean(x32, axis=0, keepdims=True)
    return _grad_sq_norm_groups(d, use_pallas)


def _consensus_sq_tree(params_G) -> jax.Array:
    """Per-group ||x_g - x̄||² summed over every pytree leaf -> (G,)."""
    total = None
    for leaf in jax.tree.leaves(params_G):
        x = leaf.astype(jnp.float32)
        d = x - jnp.mean(x, axis=0, keepdims=True)
        part = jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim)))
        total = part if total is None else total + part
    return total


def _residual_sq_groups(res, n_groups: int) -> jax.Array:
    """Per-group squared mass of a codec's error-feedback residual ->
    (G,); zeros when the stream's codec carries none (width codecs,
    identity) so the codec_err/<stream> key is always present."""
    if res is None:
        return jnp.zeros((n_groups,), jnp.float32)
    total = None
    for leaf in jax.tree.leaves(res):
        x = leaf.astype(jnp.float32)
        part = jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))
        total = part if total is None else total + part
    return total


def _obs_round_metrics(exch, comm_state: dict, streams, consensus_pre,
                       consensus_post, n_groups: int) -> dict:
    """The uniform observability block every round emits (DESIGN.md
    §13): consensus distance pre/post exchange, per-stream codec error
    mass, push-sum backlog mass, participation and the static expected
    delivery rate — ALWAYS present, zeros/ones on configurations where
    the quantity is trivially inert, so the metric schema never depends
    on topology/codec/fault flags."""
    m = {"consensus_sq": consensus_pre,
         "consensus_sq_post": consensus_post}
    cstates = comm_state.get("codec", {})
    for s in streams:
        m[f"codec_err/{s}"] = _residual_sq_groups(
            cstates.get(s, {}).get("residual"), n_groups)
    m["backlog_mass"] = (jnp.sum(comm_state["backlog_w"])
                         if "backlog_w" in comm_state
                         else jnp.zeros((), jnp.float32))
    part = comm_state.get("participation")
    m["participation"] = (jnp.asarray(part, jnp.float32)
                          if part is not None
                          else jnp.ones((), jnp.float32))
    m["delivery_rate"] = jnp.asarray(exch.delivery_rate, jnp.float32)
    # per-tier participation/delivery (DESIGN.md §16). Flat single-tier
    # convention: the whole wire is the intra tier, so intra mirrors the
    # overall number and the (nonexistent) inter tier reports 1.0
    part_i = comm_state.get("participation_intra")
    m["participation_intra"] = (jnp.asarray(part_i, jnp.float32)
                                if part_i is not None
                                else m["participation"])
    part_x = comm_state.get("participation_inter")
    m["participation_inter"] = (jnp.asarray(part_x, jnp.float32)
                                if part_x is not None
                                else jnp.ones((), jnp.float32))
    m["delivery_rate_intra"] = jnp.asarray(exch.delivery_rate_intra,
                                           jnp.float32)
    m["delivery_rate_inter"] = jnp.asarray(exch.delivery_rate_inter,
                                           jnp.float32)
    return m


# ---------------------------------------------------------------------------
# Local round = T local steps (vmapped over groups) + one averaging step
# ---------------------------------------------------------------------------


def make_local_round(loss_fn: Callable, opt: Optimizer, cfg: LocalSGDConfig,
                     layout: Optional[packing.Layout] = None,
                     exchange: Optional["comm_mod.Exchange"] = None,
                     shardexec=None, loss_has_aux: bool = False):
    """Build ``round(state_G, batch_G) -> (state_G, metrics)``.

    loss_fn(params, batch) -> scalar; with ``loss_has_aux``, -> (scalar,
    stats), a dict of arrays (a model's ``loss_and_stats``: its
    ``expert_load`` where it has experts) that the packed round's "final"
    metrics report at the round's result.
    state_G: {"params","opt"} with leading G axis on every leaf, plus a
             "comm" entry when the exchange carries state
             (``init_state(..., exchange=...)``).
    batch_G: leaves with leading axes (G, ...) for fixed_batch or
             (G, T, ...) for microbatch mode.

    With ``layout`` (and a packed optimizer from ``optim.packed``) the
    round runs on the flat-buffer fast path: state_G["params"] is one
    (G, N) f32 buffer, every inner step is one fused update pass, and the
    buffer doubles as the wire format (see DESIGN.md §6).

    ``exchange`` selects the communication backend (repro.comm,
    DESIGN.md §8): topology x codec + exact wire-byte accounting
    (``round_.wire_bytes(state_G)`` — host-side ints, merged into the
    round record by the caller). Default:
    server/fp32 — bit-exact with the pre-comm ``average_groups``.

    ``shardexec`` (a ``sharding.shardexec.ShardExec``, packed path only)
    runs the fused update, the codec, and the exchange inside shard_map
    blocks on shard-local slices of the (G, Np) buffer — ``layout`` must
    then be the matching ``packing.ShardedLayout`` (DESIGN.md §9).
    """
    exch = _resolve_exchange(exchange, cfg, layout)
    if loss_has_aux:
        eval_fn, loss_fn = loss_fn, (lambda p, b, f=loss_fn: f(p, b)[0])
    else:
        eval_fn = lambda p, b, f=loss_fn: (f(p, b), {})
    if layout is not None or getattr(opt, "packed", False):
        if layout is None or not getattr(opt, "packed", False):
            raise ValueError(
                "packed rounds need BOTH a packing.Layout and a packed "
                "optimizer (optim.packed / optim.get(..., packed=True))")
        return _with_wire_bytes(
            _make_packed_local_round(loss_fn, eval_fn, opt, cfg, layout,
                                     exch, shardexec), exch, cfg)
    if shardexec is not None:
        raise ValueError(
            "shardexec shards the packed flat buffer — it has no meaning "
            "for the per-leaf pytree round; pass layout= and a packed "
            "optimizer (DESIGN.md §9)")
    vg = jax.value_and_grad(loss_fn)

    def fixed_batch_group(state, batch, t_i=None):
        """T_i steps of full-batch local GD on this group's shard.

        t_i: optional per-group scalar — steps beyond t_i keep the state
        unchanged (masked scan to cfg.inner_steps, the max)."""
        if cfg.threshold is not None:
            def cond(carry):
                state, t, gsq, _ = carry
                return jnp.logical_and(t < cfg.max_inner,
                                       gsq > cfg.threshold)

            def body(carry):
                state, t, _, loss0 = carry
                loss, g = vg(state["params"], batch)
                new_p, new_o = opt.step(state["params"], g, state["opt"])
                return ({"params": new_p, "opt": new_o}, t + 1,
                        grad_sq_norm(g), loss)

            loss0, g0 = vg(state["params"], batch)
            state, t, gsq, loss = jax.lax.while_loop(
                cond, body, (state, jnp.zeros((), jnp.int32),
                             grad_sq_norm(g0), loss0))
            return state, {"loss": loss, "inner_steps": t, "grad_sq": gsq}

        def inner(state, t):
            loss, g = vg(state["params"], batch)
            new_p, new_o = opt.step(state["params"], g, state["opt"])
            new = {"params": new_p, "opt": new_o}
            if t_i is not None:
                keep = t < t_i
                new = jax.tree.map(
                    lambda a, b: jnp.where(keep, a, b), new, state)
            return new, (loss, grad_sq_norm(g))

        state, (losses, gsqs) = jax.lax.scan(
            inner, state, jnp.arange(cfg.inner_steps))
        n_steps = jnp.asarray(cfg.inner_steps) if t_i is None else t_i
        return state, {"loss": losses[-1],
                       "inner_steps": n_steps,
                       "grad_sq": gsqs[-1],
                       "grad_sq_first": gsqs[0],
                       "grad_sq_traj": gsqs}

    def microbatch_group(state, batches):
        """T_i steps, one microbatch per step (practical local SGD)."""
        def inner(state, mb):
            loss, g = vg(state["params"], mb)
            new_p, new_o = opt.step(state["params"], g, state["opt"])
            return {"params": new_p, "opt": new_o}, (loss, grad_sq_norm(g))

        state, (losses, gsqs) = jax.lax.scan(inner, state, batches)
        return state, {"loss": losses[-1],
                       "inner_steps": jnp.asarray(cfg.inner_steps),
                       "grad_sq": gsqs[-1],
                       "grad_sq_first": gsqs[0],
                       "grad_sq_traj": gsqs}

    group_fn = fixed_batch_group if cfg.inner_mode == "fixed_batch" \
        else microbatch_group

    def round_(state_G, batch_G):
        st = {"params": state_G["params"], "opt": state_G["opt"]}
        mkeys = (tuple(k for k in st["opt"] if k != "count")
                 if cfg.average_opt_state else ())
        _check_comm_state(exch, state_G, mkeys)
        comm_state = state_G.get("comm", {})
        # lossy codecs transmit each stream's round delta vs these
        # (identity codecs never touch x0, keeping the default bit-exact)
        xs0 = {}
        if exch.lossy_stream("params"):
            xs0["params"] = st["params"]
        xs0.update({k: st["opt"][k] for k in mkeys
                    if exch.lossy_stream(k)})
        if cfg.t_i is not None and cfg.inner_mode == "fixed_batch":
            assert len(cfg.t_i) == cfg.n_groups, cfg.t_i
            assert max(cfg.t_i) <= cfg.inner_steps, cfg.t_i
            t_vec = jnp.asarray(cfg.t_i, jnp.int32)
            with jax.named_scope("local_steps"):
                st, metrics = jax.vmap(fixed_batch_group)(st, batch_G,
                                                          t_vec)
        else:
            with jax.named_scope("local_steps"):
                st, metrics = jax.vmap(group_fn)(st, batch_G)
        # ---- communication: the multi-stream exchange (DESIGN.md §10) ----
        # params plus (when averaging opt state) one stream per moment
        # buffer, each through its own codec; the step counter is never
        # exchanged — mixing an int32 counter through a float matmul
        # would truncate and drift it across groups, and under t_i the
        # per-group counts are meaningful
        xs = {"params": st["params"]}
        xs.update({k: st["opt"][k] for k in mkeys})
        with jax.named_scope("exchange"):
            mixed, comm_state = exch.streams(xs, xs0, comm_state)
        mixed = _clamp_nonneg_streams(mixed, opt, exch)
        new_opt = {k: mixed.get(k, v) for k, v in st["opt"].items()}
        with jax.named_scope("round_metrics"):
            metrics.update(_obs_round_metrics(
                exch, comm_state, ("params",) + mkeys,
                _consensus_sq_tree(st["params"]),
                _consensus_sq_tree(mixed["params"]), cfg.n_groups))
        out = {"params": mixed["params"], "opt": new_opt}
        if "comm" in state_G:
            out["comm"] = comm_state
        return out, metrics

    return _with_wire_bytes(round_, exch, cfg)


# ---------------------------------------------------------------------------
# Packed fast path: the same round on one flat f32 buffer per state part
# ---------------------------------------------------------------------------


def carries_leaves(n_streams: int, inner_steps: int, shardexec=None) -> bool:
    """Whether a packed round carries the state's leaves through its local
    steps instead of the flat (G, N) buffers.

    On a TPU every crossing between a (G, N) buffer and the leaf tree is
    a relayout of the whole buffer (DESIGN.md §6): U to unpack one, P to
    pack one. Carrying the buffers crosses twice a local step (params
    unpacked for the model, the gradient packed for the fused update)
    and once more to evaluate the round's result: T(U+P) + U a round.
    Carrying the leaves unpacks each of the S streams (params and the
    optimizer's moments) once at entry and packs it once at exit:
    S(U+P). So the leaves cost less exactly when S <= T. Under
    ``shardexec`` the update runs on shard-local slices of the buffer
    inside shard_map (DESIGN.md §9), so that round keeps the buffers."""
    return shardexec is None and n_streams <= inner_steps


def _where_groups(keep, new, old):
    """Per-group select over leaves (or buffers) with a leading G axis:
    ``new`` where ``keep`` (G,), else ``old``."""
    return jax.tree.map(
        lambda a, b: jnp.where(keep.reshape((-1,) + (1,) * (a.ndim - 1)),
                               a, b), new, old)


def _make_packed_local_round(loss_fn: Callable, eval_fn: Callable,
                             opt: Optimizer,
                             cfg: LocalSGDConfig, layout: packing.Layout,
                             exch: "comm_mod.Exchange", shardexec=None):
    """Flat-buffer local round (see DESIGN.md §6).

    The T-step inner loop scans over fused whole-buffer updates: grads are
    taken per group (vmapped over G) against the unpacked view of the
    buffer and packed with one concatenate; ``opt.step`` then updates all
    G*N elements in one fused pass and the round ends with a single flat
    mean over G — one all-reduce of the model per round on a mesh.

    With ``shardexec`` the update, the codec, the exchange, and the traj
    ||g||² reduction run in shard_map blocks on shard-local slices of the
    (G, Np) buffer instead of relying on GSPMD partitioning — this is what
    lets the real Pallas kernels run on a sharded mesh (DESIGN.md §9).

    cfg.metrics selects the metric contract: "final" (default — the hot
    path; per-step work is JUST the fused update, loss/||grad||^2 are
    evaluated once on the round's result) or "traj" (per-step
    trajectories, matching the pytree round's metrics exactly).

    Where ``carries_leaves`` says so, the local steps carry the state as
    float32 leaves instead: every stream is unpacked once at the round's
    start (``state_unpack``) and packed once before the exchange
    (``state_pack``), and each step updates the leaves with the packed
    optimizer's elementwise formula. The round's inputs and outputs are
    the same flat buffers either way. ``round_.buffer_path`` ("leaves" or
    "flat") and ``round_.buffer_passes`` (whole-buffer crossings a round:
    2S, or 2T plus one for the "final" evaluation) say which it took.

    Per-node t_i with a count-dependent update (adamw bias correction,
    lr schedules) runs the fused step vmapped over G with a PER-GROUP
    count vector (masked like the moments), matching the pytree path's
    per-group counters — replicated path only (DESIGN.md §10). Not on
    this path (use the pytree path): threshold (T_i = inf) mode.
    """
    assert cfg.metrics in ("traj", "final"), cfg.metrics
    packing.check_packed_index_space(layout, cfg.n_groups)
    if cfg.threshold is not None:
        raise NotImplementedError(
            "threshold (T_i=inf) mode runs on the pytree path")
    if cfg.t_i is not None and cfg.inner_mode == "microbatch":
        raise NotImplementedError(
            "t_i is only defined for fixed_batch mode (the pytree path "
            "silently ignores it for microbatch)")
    # Count-dependent updates (adamw bias correction, lr schedules) need
    # per-group step counts under t_i: the fused step runs vmapped over G
    # with a (G,) count vector instead of the shared scalar.
    per_group_count = (cfg.t_i is not None
                       and getattr(opt, "count_dependent", False))
    if per_group_count and shardexec is not None:
        raise NotImplementedError(
            "per-node t_i with a count-dependent update keeps a (G,) "
            "count vector outside the shard_map opt step; run it on the "
            "replicated packed path (DESIGN.md §10)")
    use_pallas = getattr(opt, "impl", "jnp") == "pallas"
    slayout = packing.stream_layout_for(opt, layout)
    leaves = carries_leaves(slayout.n_streams, cfg.inner_steps, shardexec)
    if leaves:
        step_vg = packing.value_and_leaf_grad(loss_fn, layout)
    else:
        step_vg = packing.value_and_flat_grad(loss_fn, layout)

    exch_streams = mix_inflight = encode_streams = None
    if shardexec is not None:
        opt_step = shardexec.opt_step(opt)
        if exch.overlap:
            mix_inflight = shardexec.mix_streams(exch)
            encode_streams = shardexec.encode_streams(exch, layout)
        else:
            exch_streams = shardexec.exchange_streams(exch, layout)
        gsq_groups = shardexec.sq_norm_groups(use_pallas)
        consensus_groups = shardexec.consensus_sq_groups(use_pallas)
    else:
        if exch.overlap:
            mix_inflight = exch.mix_inflight
            encode_streams = exch.encode_streams
        else:
            exch_streams = exch.streams

        def consensus_groups(x_G):
            return _consensus_sq_flat(x_G, use_pallas)

        if leaves:
            def opt_step(p_G, g_G, o_G):
                # one group's leaves a call, so a clipping wrapper takes
                # each group's norm over all of its leaves; the count
                # stays the shared scalar unless it is per-group
                axes = {k: (None if k == "count" and not per_group_count
                            else 0) for k in o_G}
                return jax.vmap(opt.step, in_axes=(0, 0, axes),
                                out_axes=(0, axes))(p_G, g_G, o_G)

            gsq_groups = jax.vmap(grad_sq_norm)
        else:
            opt_step = (jax.vmap(opt.step) if per_group_count
                        else opt.step)

            def gsq_groups(g_G):
                return _grad_sq_norm_groups(g_G, use_pallas)

    if cfg.t_i is not None:
        assert len(cfg.t_i) == cfg.n_groups, cfg.t_i
        assert max(cfg.t_i) <= cfg.inner_steps, cfg.t_i

    def round_(state_G, batch_G):
        mkeys = slayout.moment_streams if cfg.average_opt_state else ()
        assert set(mkeys) <= set(state_G["opt"]), (mkeys,
                                                   tuple(state_G["opt"]))
        _check_comm_state(exch, state_G, mkeys)
        had_comm = "comm" in state_G
        comm_state = state_G.get("comm", {})
        opt0 = state_G["opt"]
        if per_group_count and opt0["count"].ndim == 0:
            # first round after init: promote the shared scalar count to
            # the per-group vector the masked t_i updates need
            opt0 = {**opt0, "count": jnp.broadcast_to(
                opt0["count"], (cfg.n_groups,))}
        state_G = {"params": state_G["params"], "opt": opt0}
        # lossy codecs transmit each stream's round delta vs these
        # (identity codecs never touch x0: bit-exact + donatable)
        xs0 = {}
        if exch.lossy_stream("params"):
            xs0["params"] = state_G["params"]
        xs0.update({k: state_G["opt"][k] for k in mkeys
                    if exch.lossy_stream(k)})
        t_vec = (jnp.asarray(cfg.t_i, jnp.int32)
                 if cfg.t_i is not None else None)
        if exch.overlap:
            # delayed mixing (DESIGN.md §14): issue the PREVIOUS round's
            # mixing collective FIRST — it depends only on the in-flight
            # buffers, not on this round's local steps, so a parallel
            # backend schedules the two concurrently inside one graph
            inflight = comm_state["inflight"]
            with jax.named_scope("exchange"):
                mixed_inf = mix_inflight(inflight)

        traj = cfg.metrics == "traj"
        if leaves:
            def unpack32(buf):
                return packing.unpack(buf, layout, jnp.float32)

            with jax.named_scope("state_unpack"):
                state_G = {"params": unpack32(state_G["params"]),
                           "opt": map_moments(unpack32, state_G["opt"])}

        def body(state, t, batch_t):
            loss_G, g_G = jax.vmap(step_vg)(state["params"], batch_t)
            with jax.named_scope("opt_update"):
                new_p, new_o = opt_step(state["params"], g_G, state["opt"])
            if t_vec is not None:
                keep = t < t_vec                      # (G,)
                new_p = _where_groups(keep, new_p, state["params"])
                old_o = state["opt"]

                def mask(k, v):
                    # count stays the shared scalar (map_moments
                    # convention) unless the update is count-dependent —
                    # then it is per-group and masks like the moments
                    if k == "count":
                        return (jnp.where(keep, v, old_o[k])
                                if per_group_count else v)
                    return _where_groups(keep, v, old_o[k])

                new_o = {k: mask(k, v) for k, v in new_o.items()}
            new = {"params": new_p, "opt": new_o}
            if not traj:
                # hot path: no per-step diagnostics to materialize — XLA
                # keeps only the fused update chain
                return new, None
            gsq_G = gsq_groups(g_G)
            return new, (loss_G, gsq_G)

        ts = jnp.arange(cfg.inner_steps)
        if cfg.inner_mode == "microbatch":
            # (G, T, ...) -> (T, G, ...) so scan feeds one microbatch/step
            batches_T = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1),
                                     batch_G)
            with jax.named_scope("local_steps"):
                state_G, ys = jax.lax.scan(
                    lambda s, xs: body(s, xs[0], xs[1]),
                    state_G, (ts, batches_T))
            last_batch = jax.tree.map(lambda x: x[:, -1], batch_G)
        else:
            with jax.named_scope("local_steps"):
                state_G, ys = jax.lax.scan(
                    lambda s, t: body(s, t, batch_G), state_G, ts)
            last_batch = batch_G

        n_steps = (t_vec if t_vec is not None
                   else jnp.full((cfg.n_groups,), cfg.inner_steps,
                                 jnp.int32))
        if traj:
            losses = jnp.swapaxes(ys[0], 0, 1)        # (G, T)
            gsqs = jnp.swapaxes(ys[1], 0, 1)
            metrics = {"loss": losses[:, -1],
                       "inner_steps": n_steps,
                       "grad_sq": gsqs[:, -1],
                       "grad_sq_first": gsqs[:, 0],
                       "grad_sq_traj": gsqs}
        else:
            # one extra loss/grad eval at the round's RESULT (note: the
            # traj metrics report the grad made at step T-1 instead).
            # Evaluated per leaf — the norm needs no packed gradient, so
            # skipping the pack saves two full passes over the model.
            # The loss's stats at the result join the metrics.
            vg = jax.value_and_grad(eval_fn, has_aux=True)

            def final_eval(x, b):
                view = (packing.as_layout_dtypes(x, layout) if leaves
                        else packing.unpack_for_compute(x, layout))
                (loss, stats), g_tree = vg(view, b)
                return loss, grad_sq_norm(g_tree), stats

            with jax.named_scope("final_eval"):
                loss_G, gsq_G, stats_G = jax.vmap(final_eval)(
                    state_G["params"], last_batch)
            metrics = {"loss": loss_G,
                       "inner_steps": n_steps,
                       "grad_sq": gsq_G, **stats_G}
        if leaves:
            with jax.named_scope("state_pack"):
                state_G = {"params": packing.pack(state_G["params"], layout),
                           "opt": map_moments(
                               lambda x: packing.pack(x, layout),
                               state_G["opt"])}
        # ---- communication: flat buffers through the stream exchange ----
        # every stream (params + averaged moments) rides its own codec;
        # the step counter is never exchanged (map_moments convention)
        xs = {"params": state_G["params"]}
        xs.update({k: state_G["opt"][k] for k in mkeys})
        with jax.named_scope("round_metrics"):
            consensus_pre = consensus_groups(state_G["params"])
        if exch.overlap:
            # delayed mixing, applied one round late: p' = local(p) +
            # mix(inflight) - inflight. The correction preserves the
            # G-mean (the mix is doubly stochastic) and contracts the
            # consensus deviation like the barrier mix does — PROVIDED
            # the in-flight payload is the ROUND RESULT p' (encoded
            # below), not the raw local iterate: shipping the local
            # iterate gives the deviation recursion e' = e - e_prev +
            # drift, whose characteristic roots sit ON the unit circle
            # (it oscillates and never converges).
            with jax.named_scope("apply_inflight"):
                mixed = {k: xs[k] + (mixed_inf[k] - inflight[k])
                         for k in xs}
            mixed = _clamp_nonneg_streams(mixed, opt, exch)
            # encode this round's result as the next round's in-flight
            # payload: delta vs the round start (the same codec
            # reference the barrier path uses, so quantization error
            # vanishes with convergence)
            with jax.named_scope("encode_inflight"):
                new_inflight, comm_state = encode_streams(
                    mixed, xs0, comm_state)
            comm_state = dict(comm_state)
            comm_state["inflight"] = new_inflight
        else:
            with jax.named_scope("exchange"):
                mixed, comm_state = exch_streams(xs, xs0, comm_state)
            mixed = _clamp_nonneg_streams(mixed, opt, exch)
        new_opt = {k: mixed.get(k, v) for k, v in state_G["opt"].items()}
        with jax.named_scope("round_metrics"):
            metrics.update(_obs_round_metrics(
                exch, comm_state, ("params",) + tuple(mkeys),
                consensus_pre, consensus_groups(mixed["params"]),
                cfg.n_groups))
        out = {"params": mixed["params"], "opt": new_opt}
        if had_comm:
            out["comm"] = comm_state
        return out, metrics

    round_.buffer_path = "leaves" if leaves else "flat"
    round_.buffer_passes = (2 * slayout.n_streams if leaves
                            else 2 * cfg.inner_steps
                            + (cfg.metrics == "final"))
    return round_


# ---------------------------------------------------------------------------
# Conventional baseline: synchronous data parallelism (all-reduce per step)
# ---------------------------------------------------------------------------


def make_sync_step(loss_fn: Callable, opt: Optimizer,
                   layout: Optional[packing.Layout] = None):
    """Standard DP: grads averaged across the whole batch every step.

    With params replicated and the batch sharded over ("pod","data"), XLA
    inserts a gradient all-reduce per step — the conventional schedule the
    paper compares against.

    With ``layout`` (and a packed optimizer) the state is the flat (N,)
    buffer and the update is one fused pass per step.
    """
    if layout is not None or getattr(opt, "packed", False):
        if layout is None or not getattr(opt, "packed", False):
            raise ValueError(
                "packed sync steps need BOTH a packing.Layout and a "
                "packed optimizer")
        packing.check_packed_index_space(layout)
        use_pallas = getattr(opt, "impl", "jnp") == "pallas"
        flat_vg = packing.value_and_flat_grad(loss_fn, layout)

        def packed_step(state, batch):
            loss, g = flat_vg(state["params"], batch)
            new_p, new_o = opt.step(state["params"], g, state["opt"])
            return ({"params": new_p, "opt": new_o},
                    {"loss": loss,
                     "grad_sq": grad_sq_norm(g, use_pallas)})

        return packed_step

    vg = jax.value_and_grad(loss_fn)

    def step(state, batch):
        loss, g = vg(state["params"], batch)
        new_p, new_o = opt.step(state["params"], g, state["opt"])
        return {"params": new_p, "opt": new_o}, {"loss": loss,
                                                 "grad_sq": grad_sq_norm(g)}

    return step


# ---------------------------------------------------------------------------
# Host-level driver (for real runs on small configs / examples)
# ---------------------------------------------------------------------------


def init_state(params, opt: Optimizer, n_groups: Optional[int] = None,
               layout: Optional[packing.Layout] = None,
               exchange: Optional["comm_mod.Exchange"] = None,
               average_opt_state: bool = True):
    if layout is not None:
        buf = packing.pack(params, layout)
        state = {"params": buf, "opt": opt.init(buf)}
        if n_groups:
            def rep(x):
                return jnp.broadcast_to(x[None], (n_groups,) + x.shape)

            state = {"params": rep(buf),
                     "opt": map_moments(rep, state["opt"])}
    else:
        state = {"params": params, "opt": opt.init(params)}
        if n_groups:
            state = replicate(state, n_groups)
    if exchange is not None and exchange.stateful:
        if not n_groups:
            raise ValueError("stateful exchanges need a grouped state "
                             "(pass n_groups)")
        # moment streams ride the exchange too (DESIGN.md §10): hand the
        # exchange every moment buffer so it can allocate per-stream
        # codec state and (async) per-stream staleness buffers — but only
        # when the rounds will actually average opt state (match
        # cfg.average_opt_state here, or dead G x Np pushed_opt copies
        # ride the donated train state and every checkpoint)
        moments = ({k: v for k, v in state["opt"].items() if k != "count"}
                   if average_opt_state else {})
        state["comm"] = exchange.init(state["params"],
                                      moments=moments or None)
    return state


def server_params(state_G, layout: Optional[packing.Layout] = None):
    """The averaged (server) model from a grouped state (as a pytree)."""
    if layout is not None:
        buf = state_G["params"]
        if buf.ndim > 1:
            buf = jnp.mean(buf, axis=0)
        return packing.unpack(buf, layout)
    return jax.tree.map(lambda x: jnp.mean(x, axis=0), state_G["params"])
