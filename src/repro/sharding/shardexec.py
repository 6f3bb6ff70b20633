"""shard_map execution layer for the packed round (DESIGN.md §9).

The flat-buffer engine (§6) runs the T-step hot path as fused whole-buffer
passes, but under GSPMD the Pallas kernels are not partitionable — a
``pallas_call`` over a sharded operand silently all-gathers it — so the
mesh builders used to pin ``impl="jnp"`` AND replicate the packed buffer
within each group. This module removes both limits: the buffer shards over
the in-group mesh axes (``"fsdp"``/``"model"``) via a chunk-aligned
``packing.ShardedLayout``, and the fused optimizer kernels, the int8
quantize/dequantize codec kernels, and the ``sq_norm`` metric reduction
run inside ``jax.shard_map`` blocks on each device's LOCAL shard. The
moment streams shard exactly like the params (same ShardedLayout, §10)
and ride the same shard_map exchange via ``exchange_streams``.

Mapping (one ``ShardExec`` per mesh):

* state buffers ``(G, Np)`` carry spec ``P(group_axes, shard_axes)`` —
  one group per slice of the slow axes, ``Np/n_shards`` elements per
  device inside the group;
* the per-step optimizer update is ``shard_map(opt.step)`` — element-wise,
  zero collectives;
* the group exchange routes through ``comm.Exchange`` semantics expressed
  with collectives: server/async mean = ``psum`` over the group axes,
  ring/gossip = per-hop NEIGHBOR exchange — one ``ppermute`` per nonzero
  circulant offset of W ships O(deg·shard) wire per hop instead of the
  old all_gather's O(G·shard) (DESIGN.md §11; ``hop_impl="allgather"``
  keeps the dense hop as the bit-exact parity reference) — with per-hop
  recompression matching the replicated path;
* ``topk`` runs SHARDED (DESIGN.md §11): distributed selection — shard-
  local top-k bounds + a psum'd bisection refine the per-group threshold
  over the shard axes; entries with ``|c| >= tau`` (and never the zero
  pad) ship, at most k per group; the error-feedback residual is shard-
  local and everything unselected is re-offered next round;
* metric ``||g||²`` = shard-local ``sq_norm`` + ``psum`` over shard axes.

Parity contract (tests/test_shardexec.py + test_exchange_engine.py):
sharded packed rounds match the replicated path on the SAME
``ShardedLayout`` to fp32 tolerance for sgd/momentum/adamw × server/ring
× fp32/int8 — int8 exactly, because the stochastic-rounding noise is
generated OUTSIDE the shard_map block at the full rows shape
(``Codec.noise``) and each device consumes its own slice; the ppermute
hop is bit-exact vs the all_gather hop (same assembled (G, shard) rows,
same W-row contraction). Sharded top-k is NOT bit-matched to the
replicated exact selection (threshold rule, §11) — it is convergence-
matched (fig2 suite) and property-tested instead.

Refused here (use the replicated path): a ``downlink_codec`` (its
broadcast-reference state is not threaded through the shard_map block).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm import faults as faults_mod
from repro.comm import topology as topo_mod
from repro.optim import packing

# in-group axes a packed buffer may shard over, major-to-minor — must stay
# consistent everywhere a buffer spec is built
SHARD_AXES = ("fsdp", "model")

# psum'd bisection steps refining the sharded top-k threshold: each step
# halves the [lo, hi] bracket, so 26 resolves ~1e-8 of the value range —
# below that the unselected near-threshold mass just waits one round in
# the error-feedback residual (DESIGN.md §11)
TOPK_BISECT_ITERS = 26


@dataclasses.dataclass(frozen=True)
class ShardExec:
    """Static plan: which mesh axes carry groups vs in-group shards."""
    mesh: Mesh
    group_axes: Tuple[str, ...]    # the local-SGD G axis (pod/data)
    shard_axes: Tuple[str, ...]    # in-group buffer axes (fsdp/model)
    # ring/gossip hop collective: "ppermute" (neighbor exchange, the
    # bandwidth-optimal default — O(deg·shard) wire) or "allgather" (the
    # dense O(G·shard) hop, kept as the bit-exact parity/benchmark
    # reference — DESIGN.md §11)
    hop_impl: str = "ppermute"

    @property
    def n_shards(self) -> int:
        n = 1
        for a in self.shard_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def n_groups(self) -> int:
        n = 1
        for a in self.group_axes:
            n *= self.mesh.shape[a]
        return n

    def _entry(self, axes):
        return axes[0] if len(axes) == 1 else tuple(axes)

    def buf_spec(self) -> P:
        """Spec for a (G, Np) packed buffer: groups over the slow axes,
        the flat model axis over the in-group shard axes."""
        return P(self._entry(self.group_axes), self._entry(self.shard_axes))

    def group_spec(self) -> P:
        """Spec for per-group scalars/vectors of leading dim G."""
        return P(self._entry(self.group_axes))

    def check_layout(self, layout: packing.Layout, chunk: int = 0) -> None:
        if not isinstance(layout, packing.ShardedLayout):
            raise ValueError(
                "sharded execution needs a packing.ShardedLayout "
                "(packing.shard_layout(layout, n_shards)) — got a plain "
                "Layout whose buffer does not split into shards")
        if layout.n_shards != self.n_shards:
            raise ValueError(
                f"layout sharded {layout.n_shards}-way but the mesh's "
                f"in-group axes {self.shard_axes} hold {self.n_shards} "
                "devices")
        if chunk and layout.shard_size % chunk:
            raise ValueError(
                f"shard size {layout.shard_size} is not a multiple of the "
                f"codec chunk {chunk}; build the layout with "
                f"packing.shard_layout(..., align={chunk}) so per-chunk "
                "scales stay shard-local")

    def _gidx(self):
        """Linear group index of this device, matching how the G axis
        flattens over ``group_axes`` in the buffer spec (major-to-minor)."""
        idx = jnp.zeros((), jnp.int32)
        for a in self.group_axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    # -- fused optimizer update -------------------------------------------

    def opt_step(self, opt):
        """shard_map-wrapped ``opt.step`` on (G, Np) buffers: each device
        updates its (1, shard) block with the real fused kernel (or the
        jnp fusion); the scalar step counter rides replicated."""
        spec = self.buf_spec()

        def step(buf_G, grads_G, opt_state):
            sspec = {k: (P() if k == "count" else spec) for k in opt_state}
            f = shard_map(opt.step, mesh=self.mesh,
                          in_specs=(spec, spec, sspec),
                          out_specs=(spec, sspec), check_vma=False)
            return f(buf_G, grads_G, opt_state)

        return step

    # -- metrics -----------------------------------------------------------

    def sq_norm_groups(self, use_pallas: bool):
        """Per-group ||g||² of a (G, Np) buffer: shard-local reduction
        (Pallas sq_norm kernel or one jnp fusion) + psum over the shard
        axes -> (G,)."""
        spec = self.buf_spec()
        sax = self._entry(self.shard_axes)

        def local(g):
            if use_pallas:
                from repro.kernels import use_interpret
                from repro.kernels.sq_norm import sq_norm_groups
                part = sq_norm_groups(g, interpret=use_interpret())
            else:
                part = jnp.sum(jnp.square(g.astype(jnp.float32)), axis=-1)
            return jax.lax.psum(part, sax)

        return shard_map(local, mesh=self.mesh, in_specs=(spec,),
                         out_specs=self.group_spec(), check_vma=False)

    def consensus_sq_groups(self, use_pallas: bool):
        """Per-group consensus distance ||x_g - x̄||² of a (G, Np) buffer:
        pmean over the group axes gives the fleet mean, the deviation is
        reduced shard-local (Pallas sq_norm kernel or one jnp fusion) and
        psum'd over the shard axes -> (G,). Matches the replicated
        ``x - mean(x, axis=0)`` reduction to float32 accumulation order
        within each shard (parity ≤1e-5, DESIGN.md §13)."""
        spec = self.buf_spec()
        gax = self._entry(self.group_axes)
        sax = self._entry(self.shard_axes)

        def local(x):
            x32 = x.astype(jnp.float32)
            d = x32 - jax.lax.pmean(x32, gax)
            if use_pallas:
                from repro.kernels import use_interpret
                from repro.kernels.sq_norm import sq_norm_groups
                part = sq_norm_groups(d, interpret=use_interpret())
            else:
                part = jnp.sum(jnp.square(d), axis=-1)
            return jax.lax.psum(part, sax)

        return shard_map(local, mesh=self.mesh, in_specs=(spec,),
                         out_specs=self.group_spec(), check_vma=False)

    # -- codec-free mixing ------------------------------------------------

    def mix(self, exch):
        """Sharded ``Exchange.mix`` for ONE (G, Np) buffer: psum-mean for
        server/async, k neighbor-exchange hops + this group's W row for
        ring/gossip. Identity-codec streams ride these same ops inside
        ``exchange_streams`` (DESIGN.md §10); kept as the standalone
        codec-free utility (and the §10 bit-exactness reference)."""
        if exch.topology == "none":
            return lambda x: x
        spec = self.buf_spec()
        gax = self._entry(self.group_axes)
        hop = self._hop_fn(exch.w, gax)

        def local(x):
            if hop is None:
                return jax.lax.pmean(x, gax)
            y = x
            for _ in range(exch.mix_rounds):
                y = hop(y)
            return y

        return shard_map(local, mesh=self.mesh, in_specs=(spec,),
                         out_specs=spec, check_vma=False)

    def mix_streams(self, exch):
        """``Exchange.mix_inflight`` on sharded buffers (overlap mode,
        DESIGN.md §14): the codec-free mix of the previous round's
        in-flight payload, one ``mix`` application per stream. This is
        the collective the overlap round issues BEFORE its local-step
        block."""
        one = self.mix(exch)

        def fn(inflight: dict) -> dict:
            with jax.named_scope("mix_inflight"):
                return {k: one(v) for k, v in inflight.items()}

        return fn

    def _hop_fn(self, w_np, gax):
        """Build the one-W-hop closure for a local (1, shard) block, or
        None for mean topologies (no W).

        ``hop_impl="ppermute"`` (default): one ``ppermute`` per distinct
        nonzero circulant offset of W ships each neighbor block point-to-
        point — O(deg·shard) wire per hop for a ring (offsets exactly
        {1, G-1}); irregular gossip graphs ship the offset UNION, with
        zero-weight slots a real per-link transport would elide (the
        byte accounting counts only true edges — ``n_edge_sends``). The
        received blocks are assembled into the same (G, shard) rows the
        all_gather produced (absent neighbors stay zero) and contracted
        with this group's W row — 0-weight × 0-value terms make the
        result BIT-EXACT vs the all_gather hop.

        ``hop_impl="allgather"``: the dense O(G·shard) hop (parity and
        benchmark reference).

        With ``mrow``/``act_s`` set (an active FaultPlan, DESIGN.md §12)
        the hop is MASKED: this group's W row is gated by its
        ``matrix_mask`` row, the lost weight substitutes the receiver's
        own value (``deficit`` term — rows stay stochastic), and a
        stalled receiver keeps its block — the same arithmetic as the
        replicated ``_masked_hop_leaf``."""
        if w_np is None:
            return None
        w = jnp.asarray(w_np, jnp.float32)
        G = self.n_groups

        def contract(y, full, gidx, mrow, act_s):
            row = jnp.take(w, gidx, axis=0)                     # (G,)
            if mrow is None:
                return jnp.tensordot(row, full, axes=[[0], [0]])[None]
            rm = row * mrow
            out = jnp.tensordot(rm, full, axes=[[0], [0]])[None]
            out = out + (1.0 - jnp.sum(rm)) * y
            return jnp.where(act_s > 0, out, y)

        if self.hop_impl == "allgather":
            def hop(y, mrow=None, act_s=None):
                full = jax.lax.all_gather(y, gax, axis=0, tiled=True)
                return contract(y, full, self._gidx(), mrow, act_s)

            return hop
        if self.hop_impl != "ppermute":
            raise ValueError(f"unknown hop_impl {self.hop_impl!r} "
                             "(have 'ppermute', 'allgather')")
        offs = topo_mod.neighbor_offsets(w_np)

        def hop(y, mrow=None, act_s=None):
            gidx = self._gidx()
            full = jnp.zeros((G,) + y.shape[1:], y.dtype)
            full = jax.lax.dynamic_update_slice(full, y, (gidx, 0))
            for d in offs:
                # dest g receives the block of group (g + d) % G; the
                # flattened multi-axis order matches _gidx (major->minor)
                perm = [(src, (src - d) % G) for src in range(G)]
                recv = jax.lax.ppermute(y, gax, perm)
                full = jax.lax.dynamic_update_slice(
                    full, recv, ((gidx + d) % G, 0))
            return contract(y, full, gidx, mrow, act_s)

        return hop

    # -- sharded top-k selection (DESIGN.md §11) --------------------------

    def _topk_threshold(self, a, k: int, sax, shard_size: int):
        """Per-group selection threshold for the sharded top-k codec:
        shard-local top-k bounds the global k-th value (the shard whose
        local k-th is largest proves count(>= lo) >= k; hi = global
        amax), then ``TOPK_BISECT_ITERS`` psum'd bisection steps shrink
        the bracket. Returns ``hi`` — the conservative end, so at most k
        entries are selected (near-threshold mass defers one round into
        the error-feedback residual). ``a``: shard-local |c| (shard,)."""
        k_loc = min(k, shard_size)
        top = jax.lax.top_k(a, k_loc)[0]
        hi0 = jax.lax.pmax(top[0], sax)
        lo0 = (jax.lax.pmax(top[-1], sax) if k <= shard_size
               else jnp.zeros((), a.dtype))

        def body(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            cnt = jax.lax.psum(jnp.sum((a >= mid).astype(jnp.int32)), sax)
            big = cnt > k
            return jnp.where(big, mid, lo), jnp.where(big, hi, mid)

        _, hi = jax.lax.fori_loop(0, TOPK_BISECT_ITERS, body, (lo0, hi0))
        return hi

    @staticmethod
    def _topk_select(c, tau):
        """Threshold selection with exact error feedback on the local
        block: ship ``|c| >= tau`` (never zeros — the pad region and
        dead coordinates stay off the wire), carry the rest. The EF
        identity ``c == d_hat + residual`` holds exactly."""
        keep = (jnp.abs(c) >= tau) & (jnp.abs(c) > 0.0)
        d_hat = jnp.where(keep, c, 0.0)
        return d_hat, c - d_hat

    # -- the communication step -------------------------------------------

    def exchange_streams(self, exch, layout: packing.Layout):
        """shard_map'd ``Exchange.streams`` (DESIGN.md §10/§11): every
        stream of the round's payload — params plus averaged moment
        buffers — goes through ITS codec and the topology inside ONE
        shard_map block, semantics-matched to the replicated path (incl.
        per-hop recompression for decentralized lossy rounds, per-stream
        codec state, and per-stream async staleness buffers). Codec
        handling on the local shard:

        * fp32 / topology "none": no codec work (bit-exact semantics),
        * fp16/bf16: element-wise cast on the local block (identical
          values to the replicated path by construction),
        * int8: noise generated OUTSIDE at the full rows shape via
          ``Codec.noise``, per stream from that stream's rng counter —
          per-chunk scales and rounding bits match the replicated path
          bit-for-bit on every shard (the pallas impl runs the fused
          qdq kernel — one VMEM pass, DESIGN.md §11),
        * topk: DISTRIBUTED selection (§11) — per-group threshold from
          shard-local top-k + psum'd bisection, shard-local error-
          feedback residual under ``comm_state["codec"][stream]``; at
          most k entries ship (threshold rule, not the replicated exact
          selection — convergence-matched, see module doc).

        Returns ``fn(xs, xs0, comm_state) -> (mixed, new_comm_state)``
        over ``{stream: (G, Np) buffer}`` dicts.

        Fault injection (DESIGN.md §12): an active ``exch.fault_plan``
        generates its delivery/liveness masks OUTSIDE the shard_map
        block at full (G,)/(G, G) shape — the same pattern as the int8
        rounding noise — so the sharded round consumes IDENTICAL masks
        to the replicated path; push_sum dispatches to its own
        ratio-consensus block (``_push_sum_fn``).
        """
        if exch.topology == "push_sum":
            return self._push_sum_fn(exch, layout)
        if exch.topology == "hierarchical":
            return self._hier_fn(exch, layout)
        for c in (exch.codec, exch.mcodec):
            if not (c.shardable or c.identity):
                raise NotImplementedError(
                    f"codec {c.name!r} is not shardable — run it on the "
                    "replicated path (DESIGN.md §9)")
        if exch.downlink_codec is not None:
            raise NotImplementedError(
                "downlink_codec is replicated-path only: its broadcast-"
                "reference state is not threaded through the shard_map "
                "exchange (DESIGN.md §11)")
        if exch.topology == "async_stale" and exch.codec.topk_frac > 0:
            raise NotImplementedError(
                "async_stale + topk: the staleness schedule drops "
                "non-pushing rounds, error feedback assumes delivery "
                "(DESIGN.md §8)")
        for c in (exch.codec, exch.mcodec):
            if (not c.identity) and c.chunk > 0:
                self.check_layout(layout, c.chunk)
        self.check_layout(layout)
        hops = exch.mix_rounds if exch.w is not None else 1
        spec = self.buf_spec()
        gax = self._entry(self.group_axes)
        sax = self._entry(self.shard_axes)
        hop = self._hop_fn(exch.w, gax)
        G = self.n_groups
        shard_size = layout.shard_size
        dummy_spec = P(None, None)
        plan = exch.fault_plan
        faulty = plan is not None and exch.topology != "none"
        # faulty server keeps the async-style per-stream staleness
        # buffers: a dropped push contributes its last delivered model
        buffered = (exch.topology == "async_stale"
                    or (faulty and exch.topology == "server"))

        def is_lossy(codec):
            return (not codec.identity) and exch.topology != "none"

        def compress_local(codec, y, ref, u):
            d = y - ref
            if codec.chunk > 0:
                rows = d.reshape(-1, codec.chunk)
                out = codec.compress_rows(rows, u.reshape(rows.shape))
                return ref + out.reshape(d.shape)
            d_hat, _ = codec.compress(d, {})
            return ref + d_hat

        def fn(xs, xs0, comm_state):
            names = tuple(xs)
            codecs = {k: exch.stream_codec(k) for k in names}
            lossy = {k: is_lossy(codecs[k]) for k in names}
            chunked = {k: lossy[k] and codecs[k].chunk > 0 for k in names}
            selective = {k: lossy[k] and codecs[k].topk_frac > 0
                         for k in names}
            k_sel = {k: max(1, int(round(codecs[k].topk_frac
                                         * layout.padded)))
                     for k in names if selective[k]}
            n_compress = {k: (hops if (lossy[k] and exch.w is not None)
                              else (1 if lossy[k] else 0)) for k in names}
            new_state = dict(comm_state)
            cstates = dict(comm_state.get("codec", {}))

            def topk_step(name, y, ref, res):
                """One selective-codec application on the local block:
                distributed threshold + EF residual (DESIGN.md §11)."""
                c = (y - ref) + res
                tau = self._topk_threshold(jnp.abs(c)[0], k_sel[name],
                                           sax, shard_size)
                d_hat, res = self._topk_select(c, tau)
                return ref + d_hat, res

            def local(xs_t, x0s_t, us_t, res_t, pushed_t, fm_t, rnd):
                # fm_t: fault-mask blocks — ring/gossip (mmasks, act),
                # server/async (deliver,), else a dummy (see fn below)
                outs, new_res, new_pushed = [], [], []
                for i, k in enumerate(names):
                    codec, x, x0 = codecs[k], xs_t[i], x0s_t[i]
                    res = res_t[i]
                    if exch.w is not None:         # ring / gossip
                        y, ref = x, x0
                        for h in range(hops):
                            if selective[k]:
                                y, res = topk_step(k, y, ref, res)
                                ref = y
                            elif lossy[k]:
                                y = compress_local(
                                    codec, y, ref,
                                    us_t[i][h] if chunked[k] else None)
                                ref = y
                            if faulty:
                                y = hop(y, mrow=fm_t[0][h, 0],
                                        act_s=fm_t[1][0])
                            else:
                                y = hop(y)
                        outs.append(y)
                        new_res.append(res)
                        new_pushed.append(pushed_t[i])
                        continue
                    if selective[k]:
                        y, res = topk_step(k, x, x0, res)
                    elif lossy[k]:
                        y = compress_local(codec, x, x0,
                                           us_t[i][0] if chunked[k]
                                           else None)
                    else:
                        y = x
                    if exch.topology == "async_stale":
                        keep = ((self._gidx() + rnd)
                                % (exch.staleness + 1)) == 0
                    else:
                        keep = jnp.asarray(True)
                    if faulty and buffered:
                        arrived = fm_t[0][0] > 0
                        if selective[k]:
                            # EF deferral (DESIGN.md §12): a scheduled
                            # push that DROPPED re-offers its shipped
                            # entries (d_hat == y - x0) next round
                            res = jnp.where(
                                jnp.logical_and(keep,
                                                jnp.logical_not(arrived)),
                                res + (y - x0), res)
                        keep = jnp.logical_and(keep, arrived)
                    new_res.append(res)
                    if buffered:
                        p = jnp.where(keep, y, pushed_t[i])
                        new_pushed.append(p)
                        outs.append(jax.lax.pmean(p, gax))
                    elif exch.topology == "none":
                        outs.append(y)
                        new_pushed.append(pushed_t[i])
                    else:                          # server
                        outs.append(jax.lax.pmean(y, gax))
                        new_pushed.append(pushed_t[i])
                return tuple(outs), tuple(new_res), tuple(new_pushed)

            dummy = jnp.zeros((1, 1), jnp.float32)
            us, us_specs = [], []
            for k in names:
                if not chunked[k]:
                    us.append(dummy)
                    us_specs.append(dummy_spec)
                    continue
                chunk = codecs[k].chunk
                cnt = comm_state["codec"][k]["count"]
                rows_shape = (G * layout.padded // chunk, chunk)
                us.append(jnp.stack([codecs[k].noise(cnt + h, rows_shape)
                                     .reshape(G, -1, chunk)
                                     for h in range(n_compress[k])]))
                us_specs.append(P(None, gax, sax, None))
                cstates[k] = {"count": cnt + n_compress[k]}
            res, res_specs = [], []
            for k in names:
                if not selective[k]:
                    res.append(dummy)
                    res_specs.append(dummy_spec)
                    continue
                # the EF residual is element-wise state: it shards like
                # the stream it carries (DESIGN.md §11)
                res.append(comm_state["codec"][k]["residual"])
                res_specs.append(spec)
            pushed, pushed_specs = [], []
            for k in names:
                if not buffered:
                    pushed.append(dummy)
                    pushed_specs.append(dummy_spec)
                    continue
                pushed.append(comm_state["pushed"] if k == "params"
                              else comm_state["pushed_opt"][k])
                pushed_specs.append(spec)
            rnd = comm_state.get("round", jnp.zeros((), jnp.int32))
            # fault masks, generated OUTSIDE the block at full shape
            # (DESIGN.md §12) — the exact arrays the replicated path uses
            if faulty and exch.w is not None:
                fm = (jnp.stack([plan.matrix_mask(rnd, h, G)
                                 for h in range(hops)]),
                      plan.active_mask(rnd, G))
                fm_specs = (P(None, self._entry(self.group_axes), None),
                            self.group_spec())
            elif faulty:
                fm = (plan.push_mask(rnd, G),)
                fm_specs = (self.group_spec(),)
            else:
                fm = (dummy,)
                fm_specs = (dummy_spec,)
            x0s = tuple(xs0.get(k, xs[k]) for k in names)  # dummy when
            # the stream is not lossy (never read inside the block)
            f = shard_map(local, mesh=self.mesh,
                          in_specs=((spec,) * len(names),
                                    (spec,) * len(names),
                                    tuple(us_specs), tuple(res_specs),
                                    tuple(pushed_specs), fm_specs, P()),
                          out_specs=((spec,) * len(names),
                                     tuple(res_specs),
                                     tuple(pushed_specs)),
                          check_vma=False)
            mixed_t, new_res, new_pushed = f(
                tuple(xs[k] for k in names), x0s, tuple(us), tuple(res),
                tuple(pushed), fm, rnd)
            mixed = dict(zip(names, mixed_t))
            for i, k in enumerate(names):
                if selective[k]:
                    cstates[k] = {"residual": new_res[i]}
            if any(chunked.values()) or any(selective.values()):
                new_state["codec"] = cstates
            if buffered:
                new_state["pushed"] = new_pushed[names.index("params")]
                mnames = [k for k in names if k != "params"]
                if mnames:
                    po = dict(comm_state["pushed_opt"])
                    for k in mnames:
                        po[k] = new_pushed[names.index(k)]
                    new_state["pushed_opt"] = po
            if buffered or (faulty and exch.w is not None):
                new_state["round"] = rnd + 1
            if faulty:
                if exch.w is not None:
                    new_state["participation"] = \
                        exch._edge_participation(rnd)
                else:
                    deliver = fm[0]
                    if exch.topology == "async_stale":
                        sched = (jnp.arange(G) + rnd) \
                            % (exch.staleness + 1) == 0
                    else:
                        sched = jnp.ones((G,), bool)
                    n_sched = jnp.maximum(
                        jnp.sum(sched.astype(jnp.float32)), 1.0)
                    new_state["participation"] = (
                        jnp.sum(jnp.where(sched, deliver, 0.0)) / n_sched)
            return mixed, new_state

        return fn

    def _push_sum_fn(self, exch, layout: packing.Layout):
        """shard_map'd push-sum ratio consensus (DESIGN.md §12),
        semantics-matched to ``Exchange._push_sum_streams``: each group's
        (1, shard) block ships its equal share per circulant offset via
        ``ppermute`` (the same point-to-point transport as the ring
        hops), per-directed-edge backlog buffers shard like the params,
        and the scalar weight channel rides the group axis. The fault
        masks and liveness vector are generated OUTSIDE the block at
        full (G,) shape — identical arrays to the replicated path — so
        sharded and replicated rounds agree to fp32 tolerance (the
        arithmetic is elementwise + one ppermute per offset, in the
        same order)."""
        for c in (exch.codec, exch.mcodec):
            if not (c.identity or c.name in ("fp16", "bf16")):
                raise NotImplementedError(
                    f"push_sum + {c.name}: the push-sum wire carries "
                    "cumulative mass, not round deltas (DESIGN.md §12); "
                    "valid push_sum codecs: 'fp32', 'fp16', 'bf16'")
        self.check_layout(layout)
        G = self.n_groups
        offs = topo_mod.push_sum_offsets(G)
        hops = exch.mix_rounds
        plan = exch.fault_plan
        a = 1.0 / (len(offs) + 1.0)
        spec = self.buf_spec()
        gax = self._entry(self.group_axes)
        gspec = self.group_spec()
        gentry = self._entry(self.group_axes)

        def fn(xs, xs0, comm_state):
            del xs0
            names = tuple(xs)
            new_state = dict(comm_state)
            rnd = comm_state["round"]
            if not offs:                           # G == 1: no wire
                new_state["round"] = rnd + 1
                return dict(xs), new_state
            act = (plan.active_mask(rnd, G) if plan is not None
                   else jnp.ones((G,), jnp.float32))
            incs = jnp.stack([jnp.roll(act, d) for d in offs])
            # delivery = Bernoulli edge drop x sender liveness x receiver
            # liveness — the same product the replicated path consumes
            masks = jnp.stack(
                [jnp.stack([(plan.edge_mask(rnd, h, di, G)
                             if plan is not None
                             else jnp.ones((G,), jnp.float32))
                            * incs[di] * act
                            for di, _ in enumerate(offs)])
                 for h in range(hops)])            # (hops, n_offs, G)

            def local(xs_t, bl_t, w, blw, act_l, incs_l, masks_l):
                # shapes: x (1, shard), bl (n_offs, 1, shard), w (1,),
                # blw (n_offs, 1), act_l (1,), incs_l (n_offs, 1),
                # masks_l (hops, n_offs, 1)
                nums = [x.astype(jnp.float32) * w for x in xs_t]
                bls = list(bl_t)
                for h in range(hops):
                    new_w = jnp.where(act_l > 0, a * w, w)
                    nblw = []
                    for di, d in enumerate(offs):
                        perm = [(src, (src + d) % G) for src in range(G)]
                        recv = jax.lax.ppermute(a * w, gax, perm)
                        b = blw[di] + incs_l[di] * recv
                        m = masks_l[h, di]
                        new_w = new_w + m * b
                        nblw.append(b - m * b)
                    for i, k in enumerate(names):
                        codec = exch.stream_codec(k)
                        x = nums[i]
                        y = jnp.where(act_l > 0, a * x, x)
                        nb = []
                        for di, d in enumerate(offs):
                            perm = [(src, (src + d) % G)
                                    for src in range(G)]
                            recv = jax.lax.ppermute(a * x, gax, perm)
                            b = bls[i][di] + incs_l[di] * recv
                            t = b if codec.identity \
                                else codec.compress(b, {})[0]
                            m = masks_l[h, di]
                            y = y + m * t
                            nb.append(b - m * t)
                        nums[i] = y
                        bls[i] = jnp.stack(nb)
                    w = new_w
                    blw = jnp.stack(nblw)
                outs = tuple((nums[i] / w[..., None])
                             .astype(xs_t[i].dtype)
                             for i in range(len(names)))
                return outs, tuple(bls), w, blw

            bl_spec = P(None, gentry, self._entry(self.shard_axes))
            blw_spec = P(None, gentry)
            f = shard_map(local, mesh=self.mesh,
                          in_specs=((spec,) * len(names),
                                    (bl_spec,) * len(names),
                                    gspec, blw_spec, gspec,
                                    P(None, gentry),
                                    P(None, None, gentry)),
                          out_specs=((spec,) * len(names),
                                     (bl_spec,) * len(names),
                                     gspec, blw_spec),
                          check_vma=False)
            mixed_t, new_bl, new_mass, new_blw = f(
                tuple(xs[k] for k in names),
                tuple(comm_state["backlog"][k] for k in names),
                comm_state["mass"], comm_state["backlog_w"],
                act, incs, masks)
            backlog = dict(comm_state["backlog"])
            backlog.update(dict(zip(names, new_bl)))
            new_state["mass"] = new_mass
            new_state["backlog"] = backlog
            new_state["backlog_w"] = new_blw
            new_state["round"] = rnd + 1
            new_state["participation"] = jnp.mean(masks)
            return dict(zip(names, mixed_t)), new_state

        return fn

    def _hier_fn(self, exch, layout: packing.Layout):
        """shard_map'd two-tier hierarchical round (DESIGN.md §16),
        semantics-matched to ``Exchange._hier_streams``. Stage A mixes
        WITHIN each contiguous pod — one ``ppermute`` per pod-circulant
        offset (the contiguous tier factoring is exactly what makes the
        pod-local roll a single device permutation). Stage B is the
        cross-pod tier: pod-level push_sum ratio consensus over
        stride-``pod_size`` ppermutes with mass-conserving backlogs, or
        the leader-mean server step (a ``psum`` of the elected leaders'
        decoded payloads, int8 cross-tier codec included). Every fault
        mask, liveness vector, leader weight and rounding-noise tensor
        is generated OUTSIDE the block at full (G,) shape — the exact
        arrays the replicated path consumes — so sharded and replicated
        rounds agree to fp32 tolerance (the per-member summation order
        differs, nothing else)."""
        from repro.comm.exchange import elect_leaders
        plan = exch.fault_plan
        if plan is not None and not isinstance(plan,
                                               faults_mod.TieredFaultPlan):
            raise NotImplementedError(
                "hierarchical faults are per-tier: a flat FaultPlan does "
                "not say WHICH tier it masks — wrap it as "
                "faults.TieredFaultPlan(intra=..., inter=...); valid "
                "tiers: 'intra' (pod-internal), 'inter' (cross-pod)")
        for c in (exch.codec, exch.mcodec):
            if not (c.identity or c.name in ("fp16", "bf16")):
                raise NotImplementedError(
                    f"hierarchical intra tier + {c.name}: pod-internal "
                    "hops carry whole-value payloads, not round deltas "
                    "(DESIGN.md §16); valid intra codecs: 'fp32', "
                    "'fp16', 'bf16' — put int8 on the cross-tier wire "
                    "via inter_codec with inter_topology='server'")
        inter_cs = ([exch.inter_codec] if exch.inter_codec is not None
                    else [exch.codec, exch.mcodec])
        for ic in inter_cs:
            if exch.inter_topology == "push_sum" and not (
                    ic.identity or ic.name in ("fp16", "bf16")):
                raise NotImplementedError(
                    f"hierarchical push_sum inter tier + {ic.name}: the "
                    "cross-pod wire carries cumulative (value, weight) "
                    "mass, not round deltas (DESIGN.md §12/§16); valid "
                    "push_sum inter codecs: 'fp32', 'fp16', 'bf16' — or "
                    "inter_topology='server' for 'int8'")
            if not (ic.shardable or ic.identity):
                raise NotImplementedError(
                    f"codec {ic.name!r} is not shardable — run it on the "
                    "replicated path (DESIGN.md §9)")
            if (not ic.identity) and ic.chunk > 0:
                self.check_layout(layout, ic.chunk)
        self.check_layout(layout)
        G = self.n_groups
        n_pods, s = exch.n_pods, exch.pod_len
        ip, xp = exch.intra_plan, exch.inter_plan
        hops = exch.mix_rounds
        offs_p = topo_mod.push_sum_offsets(n_pods)
        w_self, offs_pod, w_edge = topo_mod.ring_circulant(s)
        inter_ps = exch.inter_topology == "push_sum"
        ps_on = inter_ps and bool(offs_p)
        a_sh = 1.0 / (len(offs_p) + 1.0)
        spec = self.buf_spec()
        gax = self._entry(self.group_axes)
        sax = self._entry(self.shard_axes)
        gspec = self.group_spec()
        gentry = self._entry(self.group_axes)
        dummy_spec = P(None, None)

        def perm_pod(d):
            # member i receives the block of pod-mate (i + d) % s — the
            # pod-local circulant expressed on the flat G axis (pods are
            # contiguous, so src stays inside its own pod)
            return [(src, (src // s) * s + ((src % s - d) % s))
                    for src in range(G)]

        def perm_pods(dp):
            # cross-pod circulant: stride pod_size on the G axis, every
            # member lane carries 1/pod_size of its pod's traffic
            return [(src, (src + dp * s) % G) for src in range(G)]

        def fn(xs, xs0, comm_state):
            names = tuple(xs)
            codecs = {k: exch.stream_codec(k) for k in names}
            icodecs = {k: exch.inter_stream_codec(k) for k in names}
            rnd = comm_state["round"]
            new_state = dict(comm_state)
            cstates = dict(comm_state.get("codec", {}))
            touched = False
            dummy = jnp.zeros((1, 1), jnp.float32)

            def pod_take(x, d):
                r = x.reshape((n_pods, s) + x.shape[1:])
                return jnp.roll(r, -d, axis=1).reshape(x.shape)

            # ---- full-shape mask/noise generation (DESIGN.md §12) ----
            act_i = (ip.active_mask(rnd, G) if ip is not None
                     else jnp.ones((G,), jnp.float32))
            part_intra = jnp.ones((), jnp.float32)
            masksA, masksA_spec = dummy, dummy_spec
            delivA, delivA_spec = dummy, dummy_spec
            denA, denA_spec = dummy, dummy_spec
            if s > 1 and exch.intra_topology == "ring":
                rows = []
                for h in range(hops):
                    per = []
                    for di, d in enumerate(offs_pod):
                        bern = (ip.edge_mask(rnd, h, di, G)
                                if ip is not None
                                else jnp.ones((G,), jnp.float32))
                        per.append(bern * pod_take(act_i, d) * act_i)
                    rows.append(jnp.stack(per))
                masksA = jnp.stack(rows)       # (hops, n_offs_pod, G)
                masksA_spec = P(None, None, gentry)
                if ip is not None:
                    part_intra = jnp.mean(masksA)
            elif s > 1:                        # intra "server"
                deliv = (ip.push_mask(rnd, G) if ip is not None
                         else jnp.ones((G,), jnp.float32))
                # row d = the delivery of the payload arriving at each
                # member from its pod-mate at offset d (row 0 = self)
                delivA = jnp.stack([pod_take(deliv, d) for d in range(s)])
                delivA_spec = P(None, gentry)
                denA = jnp.repeat(
                    jnp.sum(deliv.reshape(n_pods, s), axis=1), s)
                denA_spec = gspec
                if ip is not None:
                    part_intra = jnp.mean(deliv)
            mass = blw = act_pod = incsB = masksB = dummy
            lead_w = dummy
            n_live = jnp.ones((), jnp.float32)
            part_inter = jnp.ones((), jnp.float32)
            if ps_on:
                act_x = (xp.active_mask(rnd, G) if xp is not None
                         else jnp.ones((G,), jnp.float32))
                _, pod_live = elect_leaders(act_x, n_pods)
                act_pod = jnp.repeat(pod_live, s)
                incs, msks = [], []
                for di, dp in enumerate(offs_p):
                    bern = (xp.edge_mask(rnd, 0, di, n_pods)
                            if xp is not None
                            else jnp.ones((n_pods,), jnp.float32))
                    src = jnp.roll(act_pod, dp * s)
                    incs.append(src)
                    msks.append(jnp.repeat(bern, s) * src * act_pod)
                incsB, masksB = jnp.stack(incs), jnp.stack(msks)
                mass = comm_state["mass"]
                blw = comm_state["backlog_w"]
                if xp is not None:
                    part_inter = jnp.mean(masksB)
            elif not inter_ps:                 # inter "server"
                act_x = (xp.active_mask(rnd, G) if xp is not None
                         else jnp.ones((G,), jnp.float32))
                lead_w, plive = elect_leaders(act_i * act_x, n_pods)
                n_live = jnp.maximum(jnp.sum(plive), 1.0)
                if ip is not None or xp is not None:
                    part_inter = jnp.mean(plive)
            mass_spec = gspec if ps_on else dummy_spec
            blw_spec = P(None, gentry) if ps_on else dummy_spec
            pvec_spec = gspec if ps_on else dummy_spec
            pmat_spec = P(None, gentry) if ps_on else dummy_spec
            lead_spec = gspec if not inter_ps else dummy_spec
            # inter-server chunked codecs: noise outside at the full
            # rows shape, each device consumes its slice (like the flat
            # int8 path — bit-identical scales and rounding bits)
            lossy_x = {k: (not inter_ps) and not icodecs[k].identity
                       for k in names}
            chunked_x = {k: lossy_x[k] and icodecs[k].chunk > 0
                         for k in names}
            us, us_specs = [], []
            for k in names:
                if not chunked_x[k]:
                    us.append(dummy)
                    us_specs.append(dummy_spec)
                    continue
                chunk = icodecs[k].chunk
                cnt = comm_state["codec"]["inter:" + k]["count"]
                rows_shape = (G * layout.padded // chunk, chunk)
                us.append(icodecs[k].noise(cnt, rows_shape)
                          .reshape(G, -1, chunk))
                us_specs.append(P(gax, sax, None))
                cstates["inter:" + k] = {"count": cnt + 1}
                touched = True
            bl_spec = P(None, gentry, sax)
            bls, bl_specs = [], []
            for k in names:
                if ps_on:
                    bls.append(comm_state["backlog"][k])
                    bl_specs.append(bl_spec)
                else:
                    bls.append(dummy)
                    bl_specs.append(dummy_spec)

            def local(xs_t, x0s_t, us_t, bl_t, act_l, mA_l, dA_l, den_l,
                      w_l, blw_l, actp_l, incs_l, msks_l, lw_l, nlive_l):
                # ---- stage A: pod-internal tier ----------------------
                ys = []
                for i, k in enumerate(names):
                    codec = codecs[k]
                    v = xs_t[i].astype(jnp.float32)
                    if s > 1 and exch.intra_topology == "ring":
                        for h in range(hops):
                            out = w_self * v
                            for di, d in enumerate(offs_pod):
                                recv = jax.lax.ppermute(v, gax,
                                                        perm_pod(d))
                                t = recv if codec.identity \
                                    else codec.compress(recv, {})[0]
                                m = mA_l[h, di][:, None]
                                out = out + w_edge * (m * t
                                                      + (1.0 - m) * v)
                            v = jnp.where(act_l[:, None] > 0, out, v)
                    elif s > 1:                # intra "server"
                        t0 = v if codec.identity \
                            else codec.compress(v, {})[0]
                        num = dA_l[0][:, None] * t0
                        for d in range(1, s):
                            recv = jax.lax.ppermute(v, gax, perm_pod(d))
                            t = recv if codec.identity \
                                else codec.compress(recv, {})[0]
                            num = num + dA_l[d][:, None] * t
                        m = num / jnp.maximum(den_l[:, None], 1.0)
                        ok = jnp.logical_and(act_l[:, None] > 0,
                                             den_l[:, None] > 0)
                        v = jnp.where(ok, m, v)
                    ys.append(v)
                # ---- stage B: cross-pod tier -------------------------
                if ps_on:
                    new_w = jnp.where(actp_l > 0, a_sh * w_l, w_l)
                    nblw = []
                    for di, dp in enumerate(offs_p):
                        recv = jax.lax.ppermute(a_sh * w_l, gax,
                                                perm_pods(dp))
                        b = blw_l[di] + incs_l[di] * recv
                        m = msks_l[di]
                        new_w = new_w + m * b
                        nblw.append(b - m * b)
                    outs, new_bls = [], []
                    for i, k in enumerate(names):
                        ic = icodecs[k]
                        x = ys[i] * w_l[:, None]
                        y = jnp.where(actp_l[:, None] > 0, a_sh * x, x)
                        nb = []
                        for di, dp in enumerate(offs_p):
                            recv = jax.lax.ppermute(a_sh * x, gax,
                                                    perm_pods(dp))
                            b = bl_t[i][di] + incs_l[di][:, None] * recv
                            t = b if ic.identity \
                                else ic.compress(b, {})[0]
                            m = msks_l[di][:, None]
                            y = y + m * t
                            nb.append(b - m * t)
                        outs.append((y / new_w[:, None])
                                    .astype(xs_t[i].dtype))
                        new_bls.append(jnp.stack(nb))
                    return (tuple(outs), tuple(new_bls), new_w,
                            jnp.stack(nblw))
                if inter_ps:                   # single pod: no DCN wire
                    outs = tuple(ys[i].astype(xs_t[i].dtype)
                                 for i in range(len(names)))
                    return (outs, tuple(dummy for _ in names), dummy,
                            dummy)
                outs = []                      # inter "server"
                for i, k in enumerate(names):
                    ic = icodecs[k]
                    y = ys[i]
                    if lossy_x[k]:
                        # cross-tier codec codes the round DELTA vs the
                        # round-start reference (the int8 cell)
                        x0f = x0s_t[i].astype(jnp.float32)
                        d = y - x0f
                        if chunked_x[k]:
                            rows = d.reshape(-1, ic.chunk)
                            out = ic.compress_rows(
                                rows, us_t[i].reshape(rows.shape))
                            y = x0f + out.reshape(d.shape)
                        else:
                            y = x0f + ic.compress(d, {})[0]
                    m = jax.lax.psum(lw_l[:, None] * y, gax) / nlive_l
                    y = jnp.where(act_l[:, None] > 0, m, y)
                    outs.append(y.astype(xs_t[i].dtype))
                return (tuple(outs), tuple(dummy for _ in names), dummy,
                        dummy)

            x0s = tuple(xs0.get(k, xs[k]) for k in names)  # dummy when
            # the stream's inter codec is not lossy (never read inside)
            f = shard_map(local, mesh=self.mesh,
                          in_specs=((spec,) * len(names),
                                    (spec,) * len(names),
                                    tuple(us_specs), tuple(bl_specs),
                                    gspec, masksA_spec, delivA_spec,
                                    denA_spec, mass_spec, blw_spec,
                                    pvec_spec, pmat_spec, pmat_spec,
                                    lead_spec, P()),
                          out_specs=((spec,) * len(names),
                                     tuple(bl_specs), mass_spec,
                                     blw_spec),
                          check_vma=False)
            mixed_t, new_bl, new_mass, new_blw = f(
                tuple(xs[k] for k in names), x0s, tuple(us), tuple(bls),
                act_i, masksA, delivA, denA, mass, blw, act_pod, incsB,
                masksB, lead_w, n_live)
            mixed = dict(zip(names, mixed_t))
            if ps_on:
                backlog = dict(comm_state["backlog"])
                backlog.update(dict(zip(names, new_bl)))
                new_state["mass"] = new_mass
                new_state["backlog"] = backlog
                new_state["backlog_w"] = new_blw
            if touched:
                new_state["codec"] = cstates
            n_is = exch._intra_send_count()
            n_xs = exch._inter_send_count()
            tot = n_is + n_xs
            new_state["round"] = rnd + 1
            new_state["participation"] = (
                (part_intra * n_is + part_inter * n_xs) / tot if tot > 0
                else jnp.ones((), jnp.float32))
            new_state["participation_intra"] = part_intra
            new_state["participation_inter"] = part_inter
            return mixed, new_state

        return fn

    def encode_streams(self, exch, layout: packing.Layout):
        """shard_map'd ``Exchange.encode_streams`` (overlap mode,
        DESIGN.md §14): codec-encode every stream ONCE on its local
        (1, shard) block — no mixing, no group-axis collectives —
        producing the decoded payload the overlap round puts in flight.
        Codec handling matches ``exchange_streams``: int8-family noise
        is generated OUTSIDE the block at the full rows shape (each
        device consumes its slice — bit-identical to the replicated
        encode); topk uses the distributed threshold selection with its
        shard-local EF residual (psum'd bisection over the shard axes
        only — mechanism kept intact although ``get_exchange`` refuses
        overlap x topk as unstable, DESIGN.md §14 refusal matrix).
        Returns ``fn(xs, xs0, comm_state) -> (x_hat,
        new_comm_state)``."""
        for c in (exch.codec, exch.mcodec):
            if not (c.shardable or c.identity):
                raise NotImplementedError(
                    f"codec {c.name!r} is not shardable — run it on the "
                    "replicated path (DESIGN.md §9)")
            if (not c.identity) and c.chunk > 0:
                self.check_layout(layout, c.chunk)
        self.check_layout(layout)
        spec = self.buf_spec()
        gax = self._entry(self.group_axes)
        sax = self._entry(self.shard_axes)
        G = self.n_groups
        shard_size = layout.shard_size
        dummy_spec = P(None, None)

        def compress_local(codec, y, ref, u):
            d = y - ref
            if codec.chunk > 0:
                rows = d.reshape(-1, codec.chunk)
                out = codec.compress_rows(rows, u.reshape(rows.shape))
                return ref + out.reshape(d.shape)
            d_hat, _ = codec.compress(d, {})
            return ref + d_hat

        def fn(xs, xs0, comm_state):
            names = tuple(xs)
            codecs = {k: exch.stream_codec(k) for k in names}
            lossy = {k: not codecs[k].identity for k in names}
            chunked = {k: lossy[k] and codecs[k].chunk > 0 for k in names}
            selective = {k: lossy[k] and codecs[k].topk_frac > 0
                         for k in names}
            k_sel = {k: max(1, int(round(codecs[k].topk_frac
                                         * layout.padded)))
                     for k in names if selective[k]}
            new_state = dict(comm_state)
            cstates = dict(comm_state.get("codec", {}))

            def local(xs_t, x0s_t, us_t, res_t):
                outs, new_res = [], []
                for i, k in enumerate(names):
                    codec, x, x0 = codecs[k], xs_t[i], x0s_t[i]
                    res = res_t[i]
                    if selective[k]:
                        c = (x - x0) + res
                        tau = self._topk_threshold(
                            jnp.abs(c)[0], k_sel[k], sax, shard_size)
                        d_hat, res = self._topk_select(c, tau)
                        y = x0 + d_hat
                    elif lossy[k]:
                        y = compress_local(codec, x, x0,
                                           us_t[i] if chunked[k]
                                           else None)
                    else:
                        y = x
                    outs.append(y)
                    new_res.append(res)
                return tuple(outs), tuple(new_res)

            dummy = jnp.zeros((1, 1), jnp.float32)
            us, us_specs = [], []
            for k in names:
                if not chunked[k]:
                    us.append(dummy)
                    us_specs.append(dummy_spec)
                    continue
                chunk = codecs[k].chunk
                cnt = comm_state["codec"][k]["count"]
                rows_shape = (G * layout.padded // chunk, chunk)
                us.append(codecs[k].noise(cnt, rows_shape)
                          .reshape(G, -1, chunk))
                us_specs.append(P(gax, sax, None))
                cstates[k] = {"count": cnt + 1}
            res, res_specs = [], []
            for k in names:
                if not selective[k]:
                    res.append(dummy)
                    res_specs.append(dummy_spec)
                    continue
                res.append(comm_state["codec"][k]["residual"])
                res_specs.append(spec)
            x0s = tuple(xs0.get(k, xs[k]) for k in names)  # dummy when
            # the stream is not lossy (never read inside the block)
            f = shard_map(local, mesh=self.mesh,
                          in_specs=((spec,) * len(names),
                                    (spec,) * len(names),
                                    tuple(us_specs), tuple(res_specs)),
                          out_specs=((spec,) * len(names),
                                     tuple(res_specs)),
                          check_vma=False)
            out_t, new_res = f(tuple(xs[k] for k in names), x0s,
                               tuple(us), tuple(res))
            for i, k in enumerate(names):
                if selective[k]:
                    cstates[k] = {"residual": new_res[i]}
            if any(chunked.values()) or any(selective.values()):
                new_state["codec"] = cstates
            return dict(zip(names, out_t)), new_state

        return fn

    def exchange(self, exch, layout: packing.Layout):
        """Single-stream convenience wrapper over ``exchange_streams``:
        (x_G, x0_G, comm_state) -> (mixed_x_G, new_comm_state) for the
        params buffer only (the pre-§10 signature, kept for tests)."""
        fn = self.exchange_streams(exch, layout)

        def one(x_G, x0_G, comm_state):
            xs0 = {} if x0_G is None else {"params": x0_G}
            mixed, new_state = fn({"params": x_G}, xs0, comm_state)
            return mixed["params"], new_state

        return one


def plan_for(mesh: Mesh, require: bool = False,
             hop_impl: str = "ppermute") -> Optional[ShardExec]:
    """The mesh's sharded-execution plan, or None when neither the group
    axes nor an in-group axis has more than one device (the replicated
    path is then both correct and free — nothing to place). A mesh whose
    only axis larger than 1 is a group axis gets a plan with one shard
    per group: each device holds its group's whole buffer. ``hop_impl``
    selects the ring/gossip hop collective (DESIGN.md §11)."""
    group_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    in_group = tuple(a for a in SHARD_AXES if a in mesh.axis_names)
    shard_axes = tuple(a for a in in_group if mesh.shape[a] > 1)
    if not shard_axes and any(mesh.shape[a] > 1 for a in group_axes):
        # groups on separate devices, each holding its whole buffer: one
        # in-group axis of size 1 keeps the (G, Np) spec two-dimensional
        shard_axes = in_group[:1]
    if not shard_axes:
        if require:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has no group axis and no "
                f"in-group axis ({'/'.join(SHARD_AXES)}) larger than 1 "
                "to place the packed buffer over")
        return None
    return ShardExec(mesh=mesh, group_axes=group_axes,
                     shard_axes=shard_axes, hop_impl=hop_impl)
