"""Fused AdamW update Pallas kernel.

The local-GD inner loop is the hot path of the paper's algorithm (T steps
per communication); this kernel fuses the whole element-wise update
(moment updates + bias correction + decay + write-back) into one VMEM pass
with three outputs, instead of the ~10 separate HLO element-wise ops."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import as_rows, flat_blocks, named_pallas_call


def _kernel(p_ref, g_ref, m_ref, v_ref, bc_ref, po_ref, mo_ref, vo_ref,
            *, lr, b1, b2, eps, wd):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    bc1 = bc_ref[0]
    bc2 = bc_ref[1]
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    po_ref[...] = (p - lr * (upd + wd * p)).astype(po_ref.dtype)
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def fused_adamw(p, g, m, v, *, count, lr, b1=0.9, b2=0.999, eps=1e-8,
                wd=0.0, block: int = 65536, interpret: bool = True):
    """Packed buffers p, g, m, v of shape (N,) or (G, N); count = post-
    increment step number. Returns (new_p, new_m, new_v)."""
    c = jnp.asarray(count, jnp.float32)
    bc = jnp.stack([1.0 - b1 ** c, 1.0 - b2 ** c])
    p2 = as_rows(p)
    bs, grid = flat_blocks(p2.shape, block)
    spec = pl.BlockSpec(bs, lambda i: (0, i))
    outs = named_pallas_call(
        "fused_adamw",
        functools.partial(_kernel, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((2,), lambda i: (0,))],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, p.dtype),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
        ],
        interpret=interpret,
    )(p2, as_rows(g), as_rows(m), as_rows(v), bc)
    return tuple(o.reshape(p.shape) for o in outs)
