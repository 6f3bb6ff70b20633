"""Step builders for the dry-run / launcher: per (arch, input-shape, mesh)
produce a jit-able step function plus abstract inputs and shardings.

Modes (from InputShape.kind):
  train    local-SGD round (paper Alg 1: T local steps + averaging) or the
           conventional sync-DP baseline
  prefill  forward over the full sequence + last-position logits
  decode   one token against a KV cache of cache_len (sliding window for
           long_500k on attention archs — see DESIGN.md)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import comm as comm_mod
from repro import obs
from repro import optim
from repro.configs.base import ArchConfig, InputShape
from repro.core import localsgd as lsgd
from repro.optim import packing
from repro.models import build_model
from repro.sharding import shardexec as shx
from repro.sharding import specs as sh

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class BuiltStep:
    fn: Any                       # callable to jit
    args: Tuple                   # abstract args (ShapeDtypeStructs)
    in_shardings: Tuple
    out_shardings: Any
    meta: Dict[str, Any]
    # args to donate when jitting (train states: XLA updates the model in
    # place over the T-step round instead of double-buffering it)
    donate_argnums: Tuple[int, ...] = ()


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch inputs (ShapeDtypeStruct stand-ins + shardings)
# ---------------------------------------------------------------------------


def batch_abstract(cfg: ArchConfig, batch_dims: Tuple[int, ...],
                   seq_len: int, mesh: Mesh, leading_group: bool,
                   inner_axis: Optional[str] = None):
    """Abstract model inputs with leading batch dims (e.g. (G, b) or (B,)).

    inner_axis: mesh axis the per-group batch dim shards over — "fsdp"
    under the fsdp policy, "model" under the dp policy (params
    replicated, the model axis acts as extra data parallelism)."""
    dp = sh.dp_axes(mesh)
    lead = P(dp) if leading_group else sh.batch_spec(mesh, batch_dims[0],
                                                     False)
    pad: Tuple = (None,) * (len(batch_dims) - 1)
    if (inner_axis and len(batch_dims) > 1
            and inner_axis in mesh.axis_names
            and batch_dims[1] % mesh.shape[inner_axis] == 0):
        pad = (inner_axis,) + (None,) * (len(batch_dims) - 2)
    toks = SDS(batch_dims + (seq_len,), jnp.int32)
    spec_t = P(*(tuple(lead) + pad + (None,)))
    batch = {"tokens": toks}
    specs = {"tokens": spec_t}
    if cfg.family == "vlm":
        batch["patches"] = SDS(batch_dims + (cfg.n_patches, cfg.d_model),
                               jnp.float32)
        specs["patches"] = P(*(tuple(lead) + pad + (None, None)))
    if cfg.family == "audio":
        batch["frames"] = SDS(batch_dims + (cfg.n_frames, cfg.d_model),
                              jnp.float32)
        specs["frames"] = P(*(tuple(lead) + pad + (None, None)))
    return batch, specs


# ---------------------------------------------------------------------------
# Cache shardings (decode)
# ---------------------------------------------------------------------------


def cache_specs(cfg: ArchConfig, cache_abs, mesh: Mesh, batch: int):
    """Name/rank-based PartitionSpecs for decode caches (see DESIGN.md):
    batch over ("pod","data") when divisible; for attention KV the cache
    *length* axis shards over "model" when divisible (kv heads rarely divide
    16); mamba heads / xlstm channels shard over "model"."""
    bx = sh.serve_batch_axes(mesh)
    bsz = 1
    for a in bx:
        bsz *= mesh.shape[a]
    b_ax = bx if (bsz > 1 and batch % bsz == 0) else None
    msz = mesh.shape.get("model", 1)

    def for_leaf(path, leaf):
        names = [str(getattr(p, "key", "")) for p in path]
        shp = leaf.shape
        if "slot_pos" in names:
            return P()
        if names[0] == "kv" or "cross" in names[0]:
            # (L, B, W, KV, hd)
            w_ax = "model" if shp[2] % msz == 0 else None
            return P(None, b_ax, w_ax, None, None)
        if names[0] == "mamba":
            if names[-1] == "conv":      # (L, B, K, di)
                return P(None, b_ax, None,
                         "model" if shp[3] % msz == 0 else None)
            # ssm (L, B, H, N, P)
            return P(None, b_ax, "model" if shp[2] % msz == 0 else None,
                     None, None)
        if names[0] == "mlstm":
            # (g, n_m, B, H, P, P) or (g, n_m, B, H, P)
            h_ax = "model" if shp[3] % msz == 0 else None
            rest = (None,) * (len(shp) - 4)
            return P(None, None, b_ax, h_ax, *rest)
        if names[0] == "slstm":
            # (g, B, di)
            return P(None, b_ax, "model" if shp[2] % msz == 0 else None)
        return P(*( (None,) * len(shp) ))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_abs)
    out = [for_leaf(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------


def build_train_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                     *, t_inner: int = 4, opt_name: str = "sgd",
                     lr: float = 1e-3, mode: str = "localsgd",
                     schedule: str = "rect", moe_impl: Optional[str] = None,
                     policy: str = "tp", packed: bool = False,
                     comm: str = "server", codec: str = "fp32",
                     mix_rounds: int = 1, staleness: int = 1,
                     impl: str = "auto", moment_codec: str = "fp32",
                     downlink_codec: str = "", drop_rate: float = 0.0,
                     stall_rate: float = 0.0,
                     fault_seed: int = 0,
                     overlap: bool = False, n_pods: int = 0,
                     intra_topology: str = "ring",
                     inter_topology: str = "push_sum",
                     inter_codec: str = "",
                     intra_drop_rate: float = 0.0,
                     intra_stall_rate: float = 0.0) -> BuiltStep:
    """policy (see sharding.specs.spec_for): "tp" (baseline), "dp"
    (replicate params, batch over the model axis — small archs), or "tp"
    on an fsdp mesh (params additionally sharded over "fsdp").

    packed=True runs the round on the flat-buffer fast path (DESIGN.md
    §6): state leaves are single (G, Np) f32 buffers, every inner step is
    one fused update pass, and the state args are donated. On meshes with
    an in-group axis ("model"/"fsdp" > 1) the buffer additionally shards
    over those axes and the fused/codec kernels run inside shard_map
    blocks on the local shards (sharded execution, DESIGN.md §9);
    otherwise the buffer is replicated within a group.

    comm/codec select the exchange backend (repro.comm, DESIGN.md §8) for
    local-SGD rounds; moment_codec applies to every moment stream of the
    payload (DESIGN.md §10 — fp32/fp16/bf16/int8, topk refused). Flat-only
    codecs (int8) on either stream need packed=True; comm state (per-stream
    codec residuals, staleness buffers) rides in the train state and
    shares its shardings.

    impl picks the packed-update/codec kernels: "pallas" (fused kernels —
    sharded or single-device packed paths only), "jnp" (one XLA fusion),
    "auto" (pallas where supported, else jnp)."""
    if mode == "sync" and (comm != "server" or codec != "fp32"
                           or moment_codec != "fp32" or downlink_codec
                           or drop_rate or stall_rate or overlap
                           or n_pods or inter_codec
                           or intra_drop_rate or intra_stall_rate):
        raise ValueError(
            "comm/codec/fault flags select the local-SGD model exchange; "
            "sync-DP all-reduces gradients every step and has no "
            "exchange — drop the flags or use mode='localsgd'")
    if moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    model = build_model(cfg, schedule=schedule)
    if "fsdp" in mesh.axis_names and policy == "tp" and not packed:
        # (packed rounds skip the per-layer fsdp hooks: the fsdp axis
        # shards the flat buffer itself via shardexec, and constraining
        # the unpacked views would fight that layout)
        model = _fsdp_model(cfg, mesh, model, schedule,
                            act_axes=("fsdp",))
    if cfg.param_dtype != "float32":
        from repro.models.layers import is_pdef
        model.defs = jax.tree.map(
            lambda d: dataclasses.replace(d, dtype=cfg.param_dtype),
            model.defs, is_leaf=is_pdef)
    if packed:
        # the packed buffer has its own sharding story (G axis + in-group
        # shard axes via shardexec); the per-tensor policies don't apply
        if policy != "tp":
            raise NotImplementedError(
                "packed train steps ignore per-tensor policies (the flat "
                "buffer shards over the in-group axes via shardexec, "
                "DESIGN.md §9); drop --packed or the policy flag")
        if mode == "sync" and "fsdp" in mesh.axis_names:
            # sync keeps the replicated (N,) buffer (no G axis, no
            # shard_map path) — refuse rather than silently record a
            # replicated profile on a mesh the caller built for sharding
            raise NotImplementedError(
                "packed sync steps keep the replicated (N,) buffer; "
                "in-group sharding is a localsgd feature (DESIGN.md §9) "
                "— drop the fsdp axis or use mode='localsgd'")
        return _build_packed_train_step(cfg, shape, mesh, model, opt_name,
                                        lr, mode, t_inner, comm, codec,
                                        mix_rounds, staleness, impl,
                                        moment_codec, downlink_codec,
                                        drop_rate, stall_rate, fault_seed,
                                        overlap, n_pods, intra_topology,
                                        inter_topology, inter_codec,
                                        intra_drop_rate, intra_stall_rate)
    if impl != "auto":
        # same no-silent-fallback rule as optim.get: the pytree round has
        # no fused-kernel path for impl to select
        raise ValueError(
            f"impl={impl!r} selects the packed fused kernels; pass "
            "packed=True (the pytree round has no Pallas path)")
    opt = optim.get(opt_name, lr)
    dp = sh.dp_axes(mesh)
    pspecs = sh.resolve_specs(model.defs, mesh, policy=policy)
    pspecs = _drop_fsdp_outside_blocks(pspecs)
    params_abs = model.abstract()

    if mode == "sync":
        step = lsgd.make_sync_step(model.loss, opt)
        B = shape.global_batch
        batch_abs, bspecs = batch_abstract(cfg, (B,), shape.seq_len, mesh,
                                           leading_group=False)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        ospecs = _opt_specs(opt_abs, pspecs, group=())
        state_abs = {"params": params_abs, "opt": opt_abs}
        sspecs = {"params": pspecs, "opt": ospecs}
        return BuiltStep(
            step, (state_abs, batch_abs),
            (_ns(mesh, sspecs), _ns(mesh, bspecs)),
            (_ns(mesh, sspecs), None),
            {"mode": "sync", "tokens": B * shape.seq_len, "t_inner": 1})

    # local-SGD round (the paper's algorithm)
    G = sh.n_groups(mesh)
    assert shape.global_batch % G == 0, (shape.global_batch, G)
    b = shape.global_batch // G
    exchange, avg_opt = _build_exchange(comm, codec, G, mix_rounds,
                                        staleness,
                                        moment_codec=moment_codec,
                                        downlink_codec=downlink_codec,
                                        drop_rate=drop_rate,
                                        stall_rate=stall_rate,
                                        fault_seed=fault_seed,
                                        overlap=overlap, n_pods=n_pods,
                                        intra_topology=intra_topology,
                                        inter_topology=inter_topology,
                                        inter_codec=inter_codec,
                                        intra_drop_rate=intra_drop_rate,
                                        intra_stall_rate=intra_stall_rate)
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner,
                               inner_mode="fixed_batch",
                               average_opt_state=avg_opt)
    round_ = lsgd.make_local_round(model.loss, opt, lcfg,
                                   exchange=exchange)

    params_G = jax.tree.map(lambda s: SDS((G,) + s.shape, s.dtype),
                            params_abs)
    pspecs_G = _drop_fsdp_outside_blocks(
        sh.resolve_specs(model.defs, mesh, leading=dp, policy=policy))
    opt_1 = jax.eval_shape(opt.init, params_abs)
    opt_G = jax.tree.map(lambda s: SDS((G,) + s.shape, s.dtype), opt_1)
    ospecs_G = _opt_specs(opt_G, pspecs_G, group=dp)
    state_abs = {"params": params_G, "opt": opt_G}
    sspecs = {"params": pspecs_G, "opt": ospecs_G}
    _add_comm_state(exchange, params_G, state_abs, sspecs, dp, G,
                    param_specs=pspecs_G,
                    moments={k: v for k, v in opt_G.items()
                             if k != "count"})
    inner_axis = None
    if policy == "dp":
        inner_axis = "model"
    elif "fsdp" in mesh.axis_names:
        inner_axis = "fsdp"
    batch_abs, bspecs = batch_abstract(cfg, (G, b), shape.seq_len, mesh,
                                       leading_group=True,
                                       inner_axis=inner_axis)
    def _n(tree):
        return sum(int(np.prod(s.shape)) if s.shape else 1
                   for s in jax.tree.leaves(tree))

    # stream-resolved wire accounting mirrors the round's
    # round_wire_bytes: each moment stream rides its own codec; the step
    # counter is never exchanged
    moment_sizes = ({k: _n(v) for k, v in opt_1.items() if k != "count"}
                    if avg_opt else {})
    n_p = _n(params_abs)
    return BuiltStep(
        round_, (state_abs, batch_abs),
        (_ns(mesh, sspecs), _ns(mesh, bspecs)),
        (_ns(mesh, sspecs), None),
        {"mode": "localsgd", "groups": G, "per_group": b,
         "tokens": shape.global_batch * shape.seq_len * t_inner,
         "t_inner": t_inner, "policy": policy,
         "param_dtype": cfg.param_dtype, "comm": exchange.name,
         "overlap": exchange.overlap,
         "wire_bytes_per_round": exchange.wire_bytes_per_round(
             n_p, moment_sizes=moment_sizes),
         "wire_bytes_up_per_round": exchange.wire_bytes_up(
             n_p, moment_sizes=moment_sizes),
         "wire_bytes_down_per_round": exchange.wire_bytes_down(
             n_p, moment_sizes=moment_sizes),
         "wire_bytes_per_round_by_stream": exchange.wire_bytes_by_stream(
             n_p, moment_sizes),
         "wire_bytes_per_round_by_tier": exchange.wire_bytes_by_tier(
             n_p, moment_sizes),
         "delivery_rate": exchange.delivery_rate,
         "metrics_schema": list(obs.round_metric_keys(
             ("params",) + tuple(moment_sizes)))})


def _packed_impl(impl: str, mesh: Mesh, sexec) -> str:
    """Resolve the fused-kernel/codec impl for a packed mesh step. With a
    sharded plan (sexec, localsgd only — sync never enters shard_map) or
    a single-device mesh any impl is executable: the kernels run on
    shard-local (or whole) buffers. Everywhere else a pallas_call is not
    GSPMD-partitionable — over the G-sharded localsgd buffer it would
    all-gather the state every step, and even over sync's replicated
    buffer it is the exact on-mesh configuration DESIGN.md §6 rules out —
    so an explicit "pallas" raises a clear error (never a silent jnp
    substitution) and "auto" resolves to "jnp"."""
    from repro.kernels import resolve_impl
    if sexec is not None or mesh.devices.size == 1:
        return resolve_impl(impl)
    if impl == "pallas":
        raise NotImplementedError(
            "impl='pallas' on a multi-device mesh only runs inside the "
            "sharded localsgd path (a pallas_call is not "
            "GSPMD-partitionable outside shard_map). Use a mesh with "
            "'model'/'fsdp' > 1 and mode='localsgd' (DESIGN.md §9), a "
            "single-device mesh, or impl='jnp'")
    return "jnp" if impl == "auto" else resolve_impl(impl)


def _build_exchange(comm: str, codec: str, n_groups: int,
                    mix_rounds: int = 1, staleness: int = 1,
                    impl: str = "jnp", moment_codec: str = "fp32",
                    downlink_codec: str = "", drop_rate: float = 0.0,
                    stall_rate: float = 0.0, fault_seed: int = 0,
                    overlap: bool = False, n_pods: int = 0,
                    intra_topology: str = "ring",
                    inter_topology: str = "push_sum",
                    inter_codec: str = "",
                    intra_drop_rate: float = 0.0,
                    intra_stall_rate: float = 0.0):
    """Exchange for a mesh step builder; ``impl`` selects the codec
    kernels and must already be resolved for the execution path
    (``_packed_impl`` — shard_map runs the Pallas quantize kernels on
    shard-local rows; the replicated fallback keeps the jnp reference).
    ``moment_codec`` applies to every moment stream (DESIGN.md §10);
    drop_rate/stall_rate/fault_seed arm the deterministic FaultPlan
    (DESIGN.md §12 — zero rates keep the exchange bit-exact fault-free).
    Returns (exchange, average_opt_state) — True on every topology since
    the per-stream staleness buffers landed."""
    exchange = comm_mod.get_exchange(comm, codec, n_groups, impl=impl,
                                     mix_rounds=mix_rounds,
                                     staleness=staleness,
                                     moment_codec=moment_codec,
                                     downlink_codec=downlink_codec,
                                     drop_rate=drop_rate,
                                     stall_rate=stall_rate,
                                     fault_seed=fault_seed,
                                     overlap=overlap, n_pods=n_pods,
                                     intra_topology=intra_topology,
                                     inter_topology=inter_topology,
                                     inter_codec=inter_codec,
                                     intra_drop_rate=intra_drop_rate,
                                     intra_stall_rate=intra_stall_rate)
    return exchange, exchange.supports_opt_state_averaging


def _add_comm_state(exchange, params_G, state_abs, sspecs, dp, G,
                    param_specs, moments=None):
    """Thread stateful-exchange memory (per-stream codec residuals,
    staleness buffers, counters) into the abstract state + shardings.
    The ``pushed`` staleness buffer and every ``pushed_opt`` stream
    mirror the params' geometry, so they take the params' OWN specs
    (keeping TP/fsdp sharding — a lead-only spec would replicate the
    whole per-group model and reshard every round); other G-leading
    leaves shard on the group axis, scalars replicate."""
    if not exchange.stateful:
        return
    comm_abs = jax.eval_shape(
        lambda p, m: exchange.init(p, moments=m), params_G, moments)
    lead = P(dp) if dp else P()

    def spec(s):
        if s.ndim >= 1 and s.shape[0] == G:
            return P(*(tuple(lead) + (None,) * (s.ndim - 1)))
        return P(*((None,) * s.ndim))

    def _lead_offset(spec_tree):
        # per-edge backlog buffers stack the stream's geometry under a
        # small leading offset axis (len(push_sum_offsets),) — replicate
        # that axis, keep the stream's own sharding behind it
        return jax.tree.map(lambda s: P(*((None,) + tuple(s))), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def for_key(k, v):
        if k == "pushed":
            return param_specs
        if k == "pushed_opt":
            return {name: param_specs for name in v}
        if k == "inflight":
            # the double-buffered in-flight payload mirrors each stream's
            # geometry exactly (DESIGN.md §14) — params' own specs, same
            # rule as the staleness buffers
            return {name: param_specs for name in v}
        if k == "backlog":
            return {name: _lead_offset(param_specs) for name in v}
        if k == "backlog_w":
            return P(*((None,) + tuple(lead)))
        if k == "codec":
            # per-stream codec state: error-feedback residuals mirror the
            # stream's geometry and must shard like the params (the
            # shard_map exchange declares them at buf_spec — a lead-only
            # spec would reshard the O(Np) residual every round);
            # counters keep the generic rule
            return {name: {kk: (param_specs if kk == "residual"
                                else jax.tree.map(spec, vv))
                           for kk, vv in sub.items()}
                    for name, sub in v.items()}
        if k == "down":
            # each stream's broadcast reference mirrors the params'
            # geometry (DESIGN.md §11) — same rule as the staleness
            # buffers; the codec state (counters) follows the generic rule
            return {name: {"ref": param_specs,
                           "state": jax.tree.map(spec, sub["state"])}
                    for name, sub in v.items()}
        return jax.tree.map(spec, v)

    cspecs = {k: for_key(k, v) for k, v in comm_abs.items()}
    state_abs["comm"] = comm_abs
    sspecs["comm"] = cspecs


def _build_packed_train_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                             model, opt_name: str, lr: float, mode: str,
                             t_inner: int, comm: str = "server",
                             codec: str = "fp32", mix_rounds: int = 1,
                             staleness: int = 1,
                             impl: str = "auto",
                             moment_codec: str = "fp32",
                             downlink_codec: str = "",
                             drop_rate: float = 0.0,
                             stall_rate: float = 0.0,
                             fault_seed: int = 0,
                             overlap: bool = False, n_pods: int = 0,
                             intra_topology: str = "ring",
                             inter_topology: str = "push_sum",
                             inter_codec: str = "",
                             intra_drop_rate: float = 0.0,
                             intra_stall_rate: float = 0.0) -> BuiltStep:
    """Flat-buffer train step (DESIGN.md §6/§9): one (G, Np) f32 buffer
    per state part, donated so XLA updates the model in place across the
    T-step round. When the mesh has an in-group axis ("model"/"fsdp" > 1)
    the buffer shards over it via a chunk-aligned ShardedLayout and the
    fused-update + codec kernels run inside shard_map on the local shards
    (shardexec); otherwise the buffer is replicated within a group and the
    update stays one GSPMD-partitioned XLA fusion (impl='pallas' refuses
    there — see _packed_impl)."""
    sexec = shx.plan_for(mesh) if mode != "sync" else None
    impl = _packed_impl(impl, mesh, sexec)
    opt = optim.get(opt_name, lr, packed=True, impl=impl)
    layout = packing.layout_of(model.abstract())
    if sexec is not None:
        layout = packing.shard_layout(layout, sexec.n_shards)

    if mode == "sync":
        # sync-DP keeps the single replicated (N,) buffer: there is no
        # G axis to pair the shard_map exchange with, and the per-step
        # gradient all-reduce dominates anyway
        step = lsgd.make_sync_step(model.loss, opt, layout=layout)
        B = shape.global_batch
        batch_abs, bspecs = batch_abstract(cfg, (B,), shape.seq_len, mesh,
                                           leading_group=False)
        buf = layout.abstract()
        opt_abs = jax.eval_shape(opt.init, buf)
        state_abs = {"params": buf, "opt": opt_abs}
        sspecs = {"params": P(), "opt": {k: P() for k in opt_abs}}
        return BuiltStep(
            step, (state_abs, batch_abs),
            (_ns(mesh, sspecs), _ns(mesh, bspecs)),
            (_ns(mesh, sspecs), None),
            {"mode": "sync", "tokens": B * shape.seq_len, "t_inner": 1,
             "packed": True, "n_flat": layout.size, "impl": impl},
            donate_argnums=(0,))

    G = sh.n_groups(mesh)
    assert shape.global_batch % G == 0, (shape.global_batch, G)
    b = shape.global_batch // G
    exchange, avg_opt = _build_exchange(comm, codec, G, mix_rounds,
                                        staleness, impl=impl,
                                        moment_codec=moment_codec,
                                        downlink_codec=downlink_codec,
                                        drop_rate=drop_rate,
                                        stall_rate=stall_rate,
                                        fault_seed=fault_seed,
                                        overlap=overlap, n_pods=n_pods,
                                        intra_topology=intra_topology,
                                        inter_topology=inter_topology,
                                        inter_codec=inter_codec,
                                        intra_drop_rate=intra_drop_rate,
                                        intra_stall_rate=intra_stall_rate)
    lcfg = lsgd.LocalSGDConfig(n_groups=G, inner_steps=t_inner,
                               inner_mode="fixed_batch",
                               average_opt_state=avg_opt)
    round_ = lsgd.make_local_round(model.loss, opt, lcfg, layout=layout,
                                   exchange=exchange, shardexec=sexec)
    dp = sh.dp_axes(mesh)
    buf_G = layout.abstract((G,))
    opt_abs = jax.eval_shape(opt.init, buf_G)
    state_abs = {"params": buf_G, "opt": opt_abs}
    lead = P(dp) if dp else P()
    buf_spec = sexec.buf_spec() if sexec is not None else lead
    sspecs = {"params": buf_spec,
              "opt": {k: (P() if k == "count" else buf_spec)
                      for k in opt_abs}}
    _add_comm_state(exchange, buf_G, state_abs, sspecs, dp, G,
                    param_specs=buf_spec,
                    moments={k: v for k, v in opt_abs.items()
                             if k != "count"})
    batch_abs, bspecs = batch_abstract(cfg, (G, b), shape.seq_len, mesh,
                                       leading_group=True)
    n_wire = layout.padded       # the buffer IS the wire format, pad incl.
    slayout = packing.stream_layout_for(opt, layout)
    moment_sizes = ({k: n_wire for k in slayout.moment_streams}
                    if avg_opt else {})
    return BuiltStep(
        round_, (state_abs, batch_abs),
        (_ns(mesh, sspecs), _ns(mesh, bspecs)),
        (_ns(mesh, sspecs), None),
        {"mode": "localsgd", "groups": G, "per_group": b,
         "tokens": shape.global_batch * shape.seq_len * t_inner,
         "t_inner": t_inner, "policy": "packed", "packed": True,
         "n_flat": layout.size, "n_flat_padded": layout.padded,
         "sharded": sexec is not None,
         "n_shards": sexec.n_shards if sexec is not None else 1,
         "impl": impl, "param_dtype": cfg.param_dtype,
         "comm": exchange.name, "overlap": exchange.overlap,
         "streams": list(slayout.streams),
         # packed rounds exchange every moment stream through its own
         # codec but never the shared step counter (mirrors
         # round_wire_bytes); totals == sums of the per-stream splits
         "wire_bytes_per_round": exchange.wire_bytes_per_round(
             n_wire, moment_sizes=moment_sizes),
         "wire_bytes_up_per_round": exchange.wire_bytes_up(
             n_wire, moment_sizes=moment_sizes),
         "wire_bytes_down_per_round": exchange.wire_bytes_down(
             n_wire, moment_sizes=moment_sizes),
         "wire_bytes_per_round_by_stream": exchange.wire_bytes_by_stream(
             n_wire, moment_sizes),
         "wire_bytes_per_round_by_tier": exchange.wire_bytes_by_tier(
             n_wire, moment_sizes),
         "delivery_rate": exchange.delivery_rate,
         "metrics_schema": list(obs.round_metric_keys(
             ("params",) + tuple(moment_sizes)))},
        donate_argnums=(0,))


def _fsdp_model(cfg, mesh: Mesh, model, schedule: str, act_axes):
    """Rebuild the model with the fsdp hooks (see DESIGN.md §5b):
    params rest fsdp-sharded; a with_sharding_constraint in the scan
    body gathers ONE layer's weights at a time (its transpose
    reduce-scatters the grads), and a second constraint pins activations
    to batch-over-act_axes — without them XLA's propagation re-shards
    seq-length activations instead."""
    from repro.models.layers import is_pdef

    blocks = model.defs.get("blocks")
    if blocks is None:
        return model
    per_layer = jax.tree.map(
        lambda d: dataclasses.replace(d, shape=d.shape[1:],
                                      axes=d.axes[1:]),
        blocks, is_leaf=is_pdef)
    gspecs = jax.tree.map(
        lambda s: P(*[None if e == "fsdp" else e for e in tuple(s)]),
        sh.resolve_specs(per_layer, mesh),
        is_leaf=lambda x: isinstance(x, P))

    def hook(p, _gs=gspecs):
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)), p, _gs)

    ax = act_axes[0] if len(act_axes) == 1 else tuple(act_axes)

    def act_hook(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(ax, None, None)))

    return build_model(cfg, schedule=schedule, layer_param_hook=hook,
                       layer_act_hook=act_hook)


def _drop_fsdp_outside_blocks(pspecs):
    """Embed / lm_head / final_norm keep vocab->model sharding only:
    fsdp-sharding their d_model axis is the matmul contraction dim of the
    LM head, which would force all-gathers of (B,S,D) activations."""
    if not isinstance(pspecs, dict):
        return pspecs
    out = {}
    for k, v in pspecs.items():
        if k == "blocks":
            out[k] = v
        else:
            out[k] = jax.tree.map(
                lambda s: P(*[None if e == "fsdp" else e
                              for e in tuple(s)]),
                v, is_leaf=lambda x: isinstance(x, P))
    return out


def _opt_specs(opt_abs, pspecs, group):
    out = {}
    for k in opt_abs:
        if k == "count":
            out[k] = P(group) if group else P()
        else:
            out[k] = pspecs
    return out


def build_prefill_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                       schedule: str = "rect", policy: str = "tp"
                       ) -> BuiltStep:
    """policy="dp": replicate params and shard the batch over every mesh
    axis — removes the TP activation all-reduces that dominate small
    archs (xlstm/zamba prefill, §Perf)."""
    model = build_model(cfg, schedule=schedule)
    if "fsdp" in mesh.axis_names and policy == "tp":
        # serving has no local-SGD groups: the whole batch shards over
        # (data, fsdp); layer hooks gather weights layer-by-layer
        model = _fsdp_model(cfg, mesh, model, schedule,
                            act_axes=sh.serve_batch_axes(mesh))
    if cfg.param_dtype != "float32":
        from repro.models.layers import is_pdef
        model.defs = jax.tree.map(
            lambda d: dataclasses.replace(d, dtype=cfg.param_dtype),
            model.defs, is_leaf=is_pdef)
    pspecs = _drop_fsdp_outside_blocks(
        sh.resolve_specs(model.defs, mesh, policy=policy))
    params_abs = model.abstract()
    B = shape.global_batch
    batch_abs, bspecs = batch_abstract(cfg, (B,), shape.seq_len, mesh,
                                       leading_group=False)
    if policy == "dp":
        # batch over ALL axes (serve axes + model)
        axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if total > 1 and B % total == 0:
            bspecs = jax.tree.map(
                lambda s: P(*((axes,) + tuple(s)[1:])), bspecs,
                is_leaf=lambda x: isinstance(x, P))

    def prefill(params, batch):
        x = model.forward(params, batch)[0]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        last = x[:, -1:]
        return jnp.einsum("bsd,dv->bsv", last,
                          head.astype(last.dtype)).astype(jnp.float32)

    return BuiltStep(
        prefill, (params_abs, batch_abs),
        (_ns(mesh, pspecs), _ns(mesh, bspecs)), None,
        {"mode": "prefill", "tokens": B * shape.seq_len})


def build_decode_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh
                      ) -> BuiltStep:
    """One-token serve step with a cache sized for the shape.

    long_500k: attention-bearing archs use the sliding-window variant
    (cache_len = cfg.long_context_window); SSM state is O(1) regardless.
    """
    model = build_model(cfg)
    if cfg.param_dtype != "float32":
        from repro.models.layers import is_pdef
        model.defs = jax.tree.map(
            lambda d: dataclasses.replace(d, dtype=cfg.param_dtype),
            model.defs, is_leaf=is_pdef)
    B = shape.global_batch
    if shape.name == "long_500k":
        cache_len = min(cfg.long_context_window, shape.seq_len)
    else:
        cache_len = shape.seq_len
    pspecs = _drop_fsdp_outside_blocks(sh.resolve_specs(model.defs, mesh))
    params_abs = model.abstract()
    cache_abs = model.init_cache(B, cache_len, abstract=True)
    cspecs = cache_specs(cfg, cache_abs, mesh, B)
    tok = SDS((B, 1), jnp.int32)
    tok_spec = sh.batch_spec(mesh, B, False)
    tspec = P(*(tuple(tok_spec) + (None,)))
    pos = SDS((), jnp.int32)

    def decode(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return BuiltStep(
        decode, (params_abs, cache_abs, tok, pos),
        (_ns(mesh, pspecs), _ns(mesh, cspecs), NamedSharding(mesh, tspec),
         NamedSharding(mesh, P())),
        None,
        {"mode": "decode", "cache_len": cache_len, "tokens": B})


def build_step(cfg: ArchConfig, shape: InputShape, mesh: Mesh, **kw
               ) -> BuiltStep:
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh,
                                  schedule=kw.get("schedule", "rect"),
                                  policy=kw.get("policy", "tp"))
    return build_decode_step(cfg, shape, mesh)
