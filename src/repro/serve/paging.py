"""Paged flat-buffer caches for the serve engine (DESIGN.md §15).

ONE f32 pool ``(n_pages, page_elems)`` holds every per-request cache:

  - KV pages: page row j of request b stores ``page_size`` tokens of
    one layer's K (or V), head-major: ``n_kv`` slabs of ``(rows,
    head_dim)`` floats (``decode_attention.page_view``), one of which the
    decode kernel (``kernels/decode_attention.py``) streams per grid
    step.
  - Recurrent-state rows: a slot's packed xLSTM/Mamba state (one flat
    buffer via ``optim/packing``) is split into ``page_elems``-wide rows
    (``packing.pad_rows``) and scattered to its own pool rows.

``page_elems`` is ``n_kv * rows * head_dim`` with ``rows >= page_size``
and a multiple of 256 — the same chunk quantum the int8
codec and ``shard_layout`` use — so pool rows stay
whole-chunk-aligned and a future sharded pool splits on the same
boundaries as the train-side wire buffers (ISSUE 9 tentpole).

Row 0 is RESERVED as the trash page: inactive batch slots route their
(masked) KV writes and reads there, so the fixed-shape decode program
never branches on activity. Real allocations start at row 1.

Allocation is whole-request and host-side (``FreeList``): a request's
full page budget (every layer's K+V tables for ``max_blocks`` blocks,
plus its state rows) is claimed at admission and freed at retirement —
admission backpressure (defer until rows free up) replaces any
mid-flight OOM path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention import page_view

ALIGN = 256        # chunk quantum shared with the int8 codec / shard_layout
TRASH_ROW = 0      # reserved pool row for masked/inactive traffic


def _round_up(n: int, q: int) -> int:
    return q * ((n + q - 1) // q)


@dataclasses.dataclass(frozen=True)
class PageGeom:
    """Static pool geometry for one (model config, engine config) pair."""
    page_size: int          # tokens per KV page
    n_kv: int               # KV heads (0 for pure-ssm: no KV pages)
    head_dim: int
    n_layers_kv: int        # layers that own KV tables (0 for pure-ssm)
    max_blocks: int         # KV page-table length per layer per slot
    state_size: int         # packed recurrent-state f32 elements per slot
    page_elems: int         # pool row width (chunk-aligned)
    state_rows: int         # pool rows per slot of recurrent state
    n_pages: int            # total pool rows incl. the trash row

    @property
    def kv_rows_per_slot(self) -> int:
        return 2 * self.n_layers_kv * self.max_blocks

    @property
    def rows_per_slot(self) -> int:
        return self.kv_rows_per_slot + self.state_rows

    def pool(self) -> jax.Array:
        return jnp.zeros((self.n_pages, self.page_elems), jnp.float32)


def make_geom(*, page_size: int, n_kv: int, head_dim: int,
              n_layers_kv: int, max_len: int, state_size: int,
              n_slots: int, slack_slots: int = 0,
              n_pages: Optional[int] = None) -> PageGeom:
    """Build the pool geometry: rows wide enough for both a KV page and
    the state-row split, and enough rows for ``n_slots + slack_slots``
    concurrent requests (or an explicit ``n_pages`` override, used by the
    backpressure test to force a tight pool)."""
    width = n_kv * head_dim
    page_elems = ALIGN
    if width:
        # n_kv head slabs of `rows` tokens each (rows >= page_size), in
        # whole ALIGN chunks
        page_elems = width * _round_up(page_size,
                                       ALIGN // math.gcd(width, ALIGN))
    max_blocks = -(-max_len // page_size) if n_layers_kv else 0
    state_rows = -(-state_size // page_elems) if state_size else 0
    geom = PageGeom(page_size=page_size, n_kv=n_kv, head_dim=head_dim,
                    n_layers_kv=n_layers_kv, max_blocks=max_blocks,
                    state_size=state_size, page_elems=page_elems,
                    state_rows=state_rows, n_pages=0)
    need = 1 + (n_slots + slack_slots) * geom.rows_per_slot
    return dataclasses.replace(geom, n_pages=n_pages if n_pages else need)


class FreeList:
    """Host-side pool-row allocator. Row 0 (trash) is never handed out."""

    def __init__(self, n_pages: int):
        self._free = list(range(n_pages - 1, 0, -1))

    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[np.ndarray]:
        """n rows as int32, or None if the pool is short (backpressure:
        the engine defers admission rather than partially allocating)."""
        if n > len(self._free):
            return None
        rows = [self._free.pop() for _ in range(n)]
        return np.asarray(rows, np.int32)

    def free(self, rows: np.ndarray) -> None:
        for r in rows.reshape(-1).tolist():
            assert r != TRASH_ROW, "trash row can never be freed"
            self._free.append(r)


# -- device-side pool access (all shapes static; everything below is
#    called inside the jit'd decode/prefill programs) -------------------


def write_token_kv(pool, rows, blk, off, vec, valid=None):
    """Scatter one decode step's per-slot K (or V) vectors into the pool.

    pool (n_pages, E); rows (B, nblk) page table for ONE layer's K or V;
    blk/off (B,) int32 block index / in-page offset; vec (B, n_kv, hd)
    f32; valid (B,) bool or None. Invalid slots write to the trash row
    at offset 0 — garbage that nothing reads (their table rows also point
    at trash, and length masking hides position 0 overwrites).
    """
    row = jnp.take_along_axis(rows, blk[:, None], axis=1)[:, 0]
    if valid is not None:
        row = jnp.where(valid, row, TRASH_ROW)
        off = jnp.where(valid, off, 0)
    _, n_kv, hd = vec.shape
    heads = jnp.arange(n_kv, dtype=jnp.int32)[None]
    pages = page_view(pool, n_kv, hd).at[row[:, None], heads,
                                         off[:, None]].set(
        vec.astype(pool.dtype))
    return pages.reshape(pool.shape)


def write_prefill_kv(pool, rows, kv):
    """Scatter a whole prefill's pages for one layer's K (or V).

    rows (nblk,) page table of the single prefilling slot; kv (nblk,
    page_size, n_kv, hd) f32, the prompt's tokens block by block. Rows
    past the prompt length still land on real (allocated) pages — their
    garbage is hidden by length masking in the kernel."""
    _, ps, n_kv, hd = kv.shape
    pages = page_view(pool, n_kv, hd).at[rows, :, :ps].set(
        jnp.swapaxes(kv, 1, 2).astype(pool.dtype))
    return pages.reshape(pool.shape)


def read_state(pool, rows, size: int):
    """Gather per-slot packed recurrent state: rows (B, state_rows) ->
    (B, size) f32 flat buffers (padding sliced off)."""
    b = rows.shape[0]
    return pool[rows].reshape(b, -1)[:, :size]


def write_state(pool, rows, buf, valid=None):
    """Scatter per-slot packed state buffers back: buf (B, size).

    Uses ``packing.pad_rows`` to split each slot's buffer into pool-row
    width; invalid slots are redirected to the trash row."""
    from repro.optim.packing import pad_rows
    tiles = pad_rows(buf.astype(pool.dtype), pool.shape[-1])  # (B, R, E)
    if valid is not None:
        rows = jnp.where(valid[:, None], rows, TRASH_ROW)
    return pool.at[rows].set(tiles)
