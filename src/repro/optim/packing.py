"""Parameter packing: pytree <-> one contiguous flat f32 buffer.

The T local steps between communications are the hot path of the paper's
algorithm (Alg 1): every inner step updates every parameter. Running that
loop leaf-by-leaf costs one HLO fusion chain per leaf per step; packing the
whole tree into a single flat float32 buffer lets the update run as ONE
fused pass (a Pallas kernel on TPU, one XLA fusion on CPU) and the
per-round server averaging lower to a single flat all-reduce.

Layout contract (see DESIGN.md §6): a ``Layout`` is a static description —
leaf order is the treedef flatten order; leaf i occupies
``buf[offsets[i]:offsets[i]+sizes[i]]`` reshaped to ``shapes[i]`` and cast
to ``dtypes[i]``. The buffer dtype is always float32. Leading batch axes
(the local-SGD G axis) stack as leading buffer axes: a G-grouped tree packs
to ``(G, size)``.

``unpack`` is static slices and ``pack`` one concatenate; on a TPU each
is a relayout of the whole buffer between the leaves' tiles and the
buffer's (DESIGN.md §6), so the packed round crosses as rarely as it
can. Gradients w.r.t. the packed buffer are taken per-leaf and packed,
NOT by differentiating through ``unpack`` — the transpose of a slice is
a pad-to-N scatter, which would materialize one full-size buffer per
leaf.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Layout:
    """Static flat-buffer layout for one parameter pytree."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    size: int                      # total number of f32 elements

    @property
    def padded(self) -> int:
        """Buffer length including trailing zero padding (== size here;
        ShardedLayout pads to a shard/chunk multiple)."""
        return self.size

    def abstract(self, leading: Tuple[int, ...] = ()):
        """ShapeDtypeStruct of the packed buffer (with leading axes)."""
        return jax.ShapeDtypeStruct(tuple(leading) + (self.padded,),
                                    jnp.float32)


@dataclasses.dataclass(frozen=True)
class ShardedLayout(Layout):
    """Shard-aware Layout (DESIGN.md §9): the buffer is zero-padded to
    ``pad_to`` — a multiple of ``n_shards * align`` — so it splits evenly
    into ``n_shards`` equal in-group shards AND every shard holds whole
    ``align``-element codec chunks (int8 per-chunk scales stay shard-local;
    no scale ever straddles a device boundary).

    The padding is invisible to ``unpack`` (static slices stop at ``size``)
    and inert under every packed optimizer: zero params with zero grads and
    zero moments stay exactly zero through sgd/momentum/adamw, quantize to
    zero, and average to zero — so the pad region never leaks into real
    elements."""
    n_shards: int = 1
    align: int = 1
    pad_to: int = 0

    @property
    def padded(self) -> int:
        return self.pad_to

    @property
    def shard_size(self) -> int:
        return self.pad_to // self.n_shards


def shard_layout(layout: Layout, n_shards: int,
                 align: int = 256) -> ShardedLayout:
    """Pad a Layout for ``n_shards``-way in-group sharding.

    align: chunk quantum every shard must hold whole multiples of —
    defaults to the int8 codec's chunk (256) so the SAME padded geometry
    serves every codec (the few KiB of zero pad is noise next to N)."""
    assert n_shards >= 1 and align >= 1, (n_shards, align)
    q = n_shards * align
    pad_to = q * ((layout.size + q - 1) // q)
    return ShardedLayout(layout.treedef, layout.shapes, layout.dtypes,
                         layout.offsets, layout.sizes, layout.size,
                         n_shards=n_shards, align=align, pad_to=pad_to)


@dataclasses.dataclass(frozen=True)
class StreamLayout:
    """Named streams over ONE buffer geometry (DESIGN.md §10).

    A packed train state is several flat buffers that all share the params
    Layout: the params themselves plus the optimizer's moment buffers
    (momentum ``mu``, adamw ``m``/``v``). A StreamLayout names them —
    ``streams[0]`` is always ``"params"``, the rest are the optimizer's
    ``moment_keys`` — so every layer (codecs, wire accounting, staleness
    buffers, checkpoints) can address "the payload" per stream instead of
    special-casing params vs opaque opt state.

    Each stream is a ``(..., base.padded)`` f32 buffer; ``stack`` gives
    the one ``(S, ..., padded)`` stacked view fused whole-payload kernels
    and codecs can consume (streams share chunk alignment, so per-chunk
    codec metadata stays stream-local in the stacked view too).
    """
    base: Layout
    streams: Tuple[str, ...]

    def __post_init__(self):
        assert self.streams and self.streams[0] == "params", self.streams
        assert len(set(self.streams)) == len(self.streams), self.streams

    @property
    def n_streams(self) -> int:
        return len(self.streams)

    @property
    def moment_streams(self) -> Tuple[str, ...]:
        return self.streams[1:]

    def index(self, name: str) -> int:
        return self.streams.index(name)

    def sizes(self) -> dict:
        """Per-stream wire element count (the buffer IS the wire format,
        padding included — same rule as the params stream)."""
        return {name: self.base.padded for name in self.streams}

    def abstract(self, leading: Tuple[int, ...] = ()) -> dict:
        return {name: self.base.abstract(leading) for name in self.streams}

    def stack(self, bufs: dict) -> jax.Array:
        """{name: (..., padded)} -> one (S, ..., padded) stacked view."""
        return jnp.stack([bufs[name] for name in self.streams])

    def unstack(self, stacked: jax.Array) -> dict:
        assert stacked.shape[0] == self.n_streams, stacked.shape
        return {name: stacked[i] for i, name in enumerate(self.streams)}


def stream_layout_for(opt, layout: Layout) -> StreamLayout:
    """StreamLayout of a packed optimizer's state on ``layout``: params
    plus the optimizer's declared moment streams (``opt.moment_keys``)."""
    return StreamLayout(layout, ("params",) + tuple(opt.moment_keys))


# XLA's packed-round lowering addresses the (G, Np) state buffers with
# int32 linear indices; a buffer past this limit dies mid-lower with a
# bare "Python int ... too large to convert to int32" (the billion-param
# dryrun overflow noted in PR 3) — refuse up front with the limit stated
INT32_INDEX_MAX = 2**31 - 1


def check_packed_index_space(layout: Layout, n_groups: int = 1) -> None:
    """Refuse packed layouts whose (n_groups, padded) state buffers
    overflow XLA's int32 index space (see INT32_INDEX_MAX)."""
    total = n_groups * layout.padded
    if total > INT32_INDEX_MAX:
        raise NotImplementedError(
            f"packed state buffer ({n_groups} group(s) x {layout.padded:,}"
            f" f32 elements = {total:,}) exceeds the int32 index space "
            f"(2**31-1 = {INT32_INDEX_MAX:,}) XLA's packed-round lowering "
            "uses — lowering would die with an int32 OverflowError. Run "
            "billion-param configs on the per-leaf pytree path (each leaf "
            "stays under the limit), or reduce the model / group count.")


def layout_of(tree) -> Layout:
    """Build the static layout from a pytree of arrays/ShapeDtypeStructs."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    return Layout(treedef, shapes, dtypes, offsets, sizes,
                  int(sum(sizes)))


def pack(tree, layout: Layout) -> jax.Array:
    """Flatten a pytree into the contiguous f32 buffer.

    Leaves may carry extra leading axes (all identical, e.g. the local-SGD
    G axis); they become leading axes of the buffer.
    """
    leaves = layout.treedef.flatten_up_to(tree)
    lead = leaves[0].shape[:leaves[0].ndim - len(layout.shapes[0])]
    flat = [l.reshape(lead + (-1,)).astype(jnp.float32) for l in leaves]
    buf = jnp.concatenate(flat, axis=-1)
    pad = layout.padded - layout.size
    if pad:
        buf = jnp.pad(buf, [(0, 0)] * (buf.ndim - 1) + [(0, pad)])
    return buf


def unpack(buf: jax.Array, layout: Layout, dtype=None):
    """Rebuild the pytree (original shapes/dtypes) from the flat buffer.

    Extra leading axes on ``buf`` are carried onto every leaf. ``dtype``
    casts every leaf to it instead of its own dtype (the leaf-carrying
    round keeps the buffer's float32).
    """
    lead = buf.shape[:-1]
    leaves = [
        buf[..., o:o + s].reshape(lead + sh).astype(dtype or dt)
        for o, s, sh, dt in zip(layout.offsets, layout.sizes,
                                layout.shapes, layout.dtypes)
    ]
    return jax.tree.unflatten(layout.treedef, leaves)


def as_layout_dtypes(tree, layout: Layout):
    """Cast every leaf of ``tree`` (float32 leaves from ``unpack(...,
    dtype=jnp.float32)``) to its dtype in ``layout``: what ``unpack``
    would have handed the model."""
    leaves = layout.treedef.flatten_up_to(tree)
    return jax.tree.unflatten(layout.treedef, [
        l.astype(dt) for l, dt in zip(leaves, layout.dtypes)])


def unpack_for_compute(buf: jax.Array, layout: Layout):
    """``unpack`` for a model's forward/backward: the leaves are
    materialized once (an optimization barrier) instead of each static
    slice + reshape being fused into its consumers. Fused into the
    matmuls of a vmapped (G, N) buffer, those views made the TPU compiler
    take minutes on a full-width round."""
    return jax.lax.optimization_barrier(unpack(buf, layout))


def chunk_rows(x: jax.Array, chunk: int) -> jax.Array:
    """(..., N) buffer -> (rows, chunk) 2-D view for per-chunk codecs.

    The flat buffer doubles as the WIRE format (repro.comm, DESIGN.md §8):
    codecs that carry per-chunk metadata (int8 scales) see the buffer as
    rows of ``chunk`` f32 elements, zero-padded to a chunk multiple —
    zeros quantize to zero, so padding never leaks into the payload."""
    n = x.shape[-1]
    pad = (-n) % chunk
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(-1, chunk)


def pad_rows(x: jax.Array, row: int) -> jax.Array:
    """(..., N) buffer -> (..., n_rows, row) zero-padded 2-D view.

    ``chunk_rows`` for callers that must KEEP the leading axes: the serve
    engine (repro.serve) scatters each batch slot's packed recurrent
    state into its own rows of the paged pool, so the row split may not
    flatten the slot axis away."""
    n = x.shape[-1]
    pad = (-n) % row
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (-1, row))


def unchunk_rows(rows: jax.Array, shape) -> jax.Array:
    """Invert ``chunk_rows``: (rows, chunk) back to the ``shape`` buffer
    (the zero padding on the last axis is sliced off)."""
    lead = tuple(shape[:-1])
    return rows.reshape(lead + (-1,))[..., :shape[-1]]


def value_and_flat_grad(loss_fn, layout: Layout):
    """``vg(buf, batch) -> (loss, flat_grad)`` for a pytree loss.

    Differentiates w.r.t. the UNPACKED tree and packs the grads (one
    concatenate) — never w.r.t. the buffer itself (see module docstring).
    The three parts run under the named scopes ``unpack``, ``fwd_bwd``
    and ``grad_pack``, which the profiler's trace attributes device time
    to (DESIGN.md §13).
    """
    vg = jax.value_and_grad(loss_fn)

    def flat_vg(buf, batch):
        with jax.named_scope("unpack"):
            tree = unpack_for_compute(buf, layout)
        with jax.named_scope("fwd_bwd"):
            loss, g_tree = vg(tree, batch)
        with jax.named_scope("grad_pack"):
            return loss, pack(g_tree, layout)

    return flat_vg


def value_and_leaf_grad(loss_fn, layout: Layout):
    """``vg(leaves, batch) -> (loss, grad_leaves)`` on float32 leaves.

    ``value_and_flat_grad`` for a round that carries the leaves instead
    of the buffer: the model sees each leaf in its layout dtype, and the
    gradient comes back in float32, as ``pack`` would store it. The
    forward and backward run under the named scope ``fwd_bwd``; there is
    nothing to unpack or pack.
    """
    vg = jax.value_and_grad(loss_fn)

    def leaf_vg(leaves, batch):
        with jax.named_scope("fwd_bwd"):
            loss, g_tree = vg(as_layout_dtypes(leaves, layout), batch)
        return loss, jax.tree.map(lambda g: g.astype(jnp.float32), g_tree)

    return leaf_vg
