"""Optimizers (built here — no external dependency).

API: ``opt = sgd(lr)``; ``state = opt.init(params)``;
``new_params, new_state = opt.step(params, grads, state)``.
All tree-structured state mirrors the param tree so the same PartitionSpecs
apply (plus replicated scalars).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    step: Callable[[Any, Any, Any], tuple]
    name: str = "opt"
    # Packed fast path (see optim.packing / DESIGN.md §6): params and grads
    # are flat f32 buffers of shape (..., N) instead of pytrees, and the
    # whole update is one fused pass. impl: "pallas" (fused kernels) or
    # "jnp" (one XLA fusion — the CPU fallback).
    packed: bool = False
    impl: str = "jnp"
    # Update depends on the step counter (adamw bias correction, lr
    # schedules). The packed round normally keeps ONE shared scalar count;
    # under per-node t_i, count-dependent packed updates run vmapped over
    # G with a per-group count vector instead (DESIGN.md §10).
    count_dependent: bool = False
    # Named moment STREAMS of the state (everything but the shared step
    # counter), in a fixed order. This is the multi-stream payload
    # contract (DESIGN.md §10): packed state is {"count"} + one flat
    # buffer per stream, each the same shape as the params buffer, so
    # comm codecs / staleness buffers / wire accounting address moments
    # by stream name instead of treating opt state as opaque.
    moment_keys: Tuple[str, ...] = ()
    # Streams that must stay >= 0 (adamw's second moment: sqrt(v) NaNs on
    # the slightly-negative values a lossy delta codec can decode). The
    # round projects these back onto [0, inf) after a LOSSY moment
    # exchange; identity moment codecs never touch them (bit-exactness).
    moment_nonneg: Tuple[str, ...] = ()


def sgd(lr: float) -> Optimizer:
    def init(params):
        return {"count": jnp.zeros((), jnp.int32)}

    def step(params, grads, state):
        new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                           params, grads)
        return new, {"count": state["count"] + 1}

    return Optimizer(init, step, "sgd")


def momentum(lr: float, beta: float = 0.9) -> Optimizer:
    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "mu": jax.tree.map(jnp.zeros_like, params)}

    def step(params, grads, state):
        mu = jax.tree.map(lambda m, g: beta * m + g.astype(m.dtype),
                          state["mu"], grads)
        new = jax.tree.map(lambda p, m: p - lr * m, params, mu)
        return new, {"count": state["count"] + 1, "mu": mu}

    return Optimizer(init, step, "momentum", moment_keys=("mu",))


def adamw(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(jnp.zeros_like, params),
                "v": jax.tree.map(jnp.zeros_like, params)}

    def step(params, grads, state):
        c = state["count"] + 1
        bc1 = 1.0 - b1 ** c.astype(jnp.float32)
        bc2 = 1.0 - b2 ** c.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(p.dtype)
            m_ = b1 * m + (1 - b1) * g
            v_ = b2 * v + (1 - b2) * jnp.square(g)
            upd_ = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            return p - lr * (upd_ + weight_decay * p), m_, v_

        flat_p, td = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(state["m"])
        flat_v = jax.tree.leaves(state["v"])
        outs = [upd(p, g, m, v) for p, g, m, v in
                zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree.unflatten(td, [o[0] for o in outs])
        new_m = jax.tree.unflatten(td, [o[1] for o in outs])
        new_v = jax.tree.unflatten(td, [o[2] for o in outs])
        return new_p, {"count": c, "m": new_m, "v": new_v}

    return Optimizer(init, step, "adamw", count_dependent=True,
                     moment_keys=("m", "v"), moment_nonneg=("v",))


# ---------------------------------------------------------------------------
# Packed fast path: flat f32 buffers + fused update kernels
# ---------------------------------------------------------------------------
#
# The T-step local loop is the paper's hot path. ``packed(name, lr)`` builds
# an optimizer whose params/grads are single contiguous f32 buffers (see
# optim.packing for the layout contract): the whole per-step update runs as
# one fused Pallas kernel (TPU) or one XLA fusion (CPU fallback), instead
# of ~10 element-wise HLO ops per pytree leaf. Buffers may carry leading
# axes (the local-SGD G axis); the kernels block the (G, N) buffer as it
# is (the raveled form compiled to 3.2 GiB more temporaries for a TPU at
# paper-lenet's width). A packed step also takes its state as trees of
# float32 leaves, as the leaf-carrying round holds it (DESIGN.md §6):
# then the same elementwise formula runs leaf by leaf, as the impl="jnp"
# branch, and XLA fuses it per leaf.


def _flat(buf) -> bool:
    """Whether a packed step got the flat buffer (else a tree of leaves)."""
    return isinstance(buf, jax.Array)


def _resolve_impl(impl: str) -> str:
    from repro.kernels import resolve_impl
    return resolve_impl(impl)


def map_moments(f, opt_state):
    """Apply ``f`` to the moment buffers of a packed opt state, leaving
    the shared scalar step counter untouched — the "'count' is the only
    shared scalar" convention. Replication and averaging go through here;
    the t_i mask in localsgd keeps the same convention inline (it needs
    old and new values per key)."""
    return {k: (v if k == "count" else f(v)) for k, v in opt_state.items()}


def packed_sgd(lr: float, *, impl: str = "auto") -> Optimizer:
    impl = _resolve_impl(impl)

    def init(buf):
        return {"count": jnp.zeros((), jnp.int32)}

    def step(buf, grads, state):
        if impl == "pallas" and _flat(buf):
            from repro.kernels import use_interpret
            from repro.kernels.fused_sgd import fused_sgd
            new = fused_sgd(buf, grads, lr=lr, interpret=use_interpret())
        else:
            new = jax.tree.map(lambda p, g: p - lr * g, buf, grads)
        return new, {"count": state["count"] + 1}

    return Optimizer(init, step, "sgd", packed=True, impl=impl)


def packed_momentum(lr: float, beta: float = 0.9, *,
                    impl: str = "auto") -> Optimizer:
    impl = _resolve_impl(impl)

    def init(buf):
        return {"count": jnp.zeros((), jnp.int32),
                "mu": jnp.zeros_like(buf)}

    def step(buf, grads, state):
        if impl == "pallas" and _flat(buf):
            from repro.kernels import use_interpret
            from repro.kernels.fused_momentum import fused_momentum
            new, mu = fused_momentum(buf, grads, state["mu"], lr=lr,
                                     beta=beta, interpret=use_interpret())
        else:
            mu = jax.tree.map(lambda m, g: beta * m + g, state["mu"], grads)
            new = jax.tree.map(lambda p, m: p - lr * m, buf, mu)
        return new, {"count": state["count"] + 1, "mu": mu}

    return Optimizer(init, step, "momentum", packed=True, impl=impl,
                     moment_keys=("mu",))


def packed_adamw(lr: float, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, weight_decay: float = 0.0, *,
                 impl: str = "auto") -> Optimizer:
    impl = _resolve_impl(impl)

    def init(buf):
        return {"count": jnp.zeros((), jnp.int32),
                "m": jnp.zeros_like(buf),
                "v": jnp.zeros_like(buf)}

    def step(buf, grads, state):
        c = state["count"] + 1
        if impl == "pallas" and _flat(buf):
            from repro.kernels import use_interpret
            from repro.kernels.fused_adamw import fused_adamw
            new, m, v = fused_adamw(
                buf, grads, state["m"], state["v"], count=c, lr=lr, b1=b1,
                b2=b2, eps=eps, wd=weight_decay, interpret=use_interpret())
        else:
            # Same math as the per-leaf adamw (bias correction unfolded)
            # so the packed path is bit-compatible up to fma reassociation.
            bc1 = 1.0 - b1 ** c.astype(jnp.float32)
            bc2 = 1.0 - b2 ** c.astype(jnp.float32)

            def upd(p, m, v):
                u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
                return p - lr * (u + weight_decay * p)

            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                             state["m"], grads)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                             state["v"], grads)
            new = jax.tree.map(upd, buf, m, v)
        return new, {"count": c, "m": m, "v": v}

    return Optimizer(init, step, "adamw", packed=True, impl=impl,
                     count_dependent=True, moment_keys=("m", "v"),
                     moment_nonneg=("v",))


_PACKED = {"sgd": packed_sgd, "momentum": packed_momentum,
           "adamw": packed_adamw}


def packed(name: str, lr: float, *, impl: str = "auto", **kw) -> Optimizer:
    """Packed (flat-buffer, fused-kernel) variant of a base optimizer."""
    return _PACKED[name](lr, impl=impl, **kw)


# ---------------------------------------------------------------------------
# Composable transforms: global-norm clipping + lr schedules
# ---------------------------------------------------------------------------


def clip_by_global_norm(opt: Optimizer, max_norm: float) -> Optimizer:
    """Wrap an optimizer so grads are clipped to a global L2 norm first.

    Works for packed optimizers too: their grad buffer may carry leading
    group axes, so the norm is taken over the model (last) axis only —
    one norm per group, matching the pytree round's per-group clipping.
    A tree of leaves (the pytree optimizers, and a packed step on one
    group's leaves) takes one norm over every leaf.
    ``dataclasses.replace`` keeps the packed/impl routing flags."""

    def step(params, grads, state):
        if opt.packed and _flat(grads):
            gsq = jnp.sum(jnp.square(grads.astype(jnp.float32)), axis=-1,
                          keepdims=True)
        else:
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads))
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(jnp.sqrt(gsq),
                                                          1e-12))
        clipped = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
        return opt.step(params, clipped, state)

    return dataclasses.replace(opt, step=step, name=opt.name + "+clip")


def cosine_schedule(base_lr: float, warmup: int, total: int,
                    min_frac: float = 0.1):
    """lr(count): linear warmup then cosine decay to min_frac*base_lr."""

    def lr_fn(count):
        c = jnp.asarray(count, jnp.float32)
        warm = base_lr * (c + 1.0) / max(warmup, 1)
        prog = jnp.clip((c - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = base_lr * (min_frac + (1 - min_frac)
                         * 0.5 * (1.0 + jnp.cos(jnp.pi * prog)))
        return jnp.where(c < warmup, warm, cos)

    return lr_fn


def with_schedule(make_opt: Callable[[float], Optimizer], lr_fn) -> Optimizer:
    """Optimizer whose lr follows lr_fn(state['count']).

    Implemented by scaling the unit-lr update: requires the base update to
    be linear in lr (true for sgd/momentum; adamw's bias-corrected update
    direction is lr-independent, so scaling is exact there too)."""
    unit = make_opt(1.0)

    def step(params, grads, state):
        lr = lr_fn(state["count"])
        new_p, new_s = unit.step(params, grads, state)
        scaled = jax.tree.map(
            lambda n, p: p + lr.astype(p.dtype) * (n - p), new_p, params)
        return scaled, new_s

    # replace() keeps the packed/impl routing flags of packed optimizers;
    # a schedule makes the update count-dependent by definition
    return dataclasses.replace(unit, step=step, name=unit.name + "+sched",
                               count_dependent=True)


def get(name: str, lr: float, *, packed: bool = False, **kw) -> Optimizer:
    table = _PACKED if packed else {"sgd": sgd, "momentum": momentum,
                                    "adamw": adamw}
    if name not in table:
        raise ValueError(f"unknown optimizer {name!r} (have {sorted(table)}"
                         f", packed={packed})")
    if not packed and "impl" in kw:
        # a clear refusal, not a TypeError (and never a silent fallback):
        # the fused Pallas kernels exist only on the flat-buffer path
        raise ValueError(
            f"impl={kw['impl']!r} selects the fused-kernel path, which "
            "only exists for packed optimizers — pass packed=True (the "
            "pytree optimizers have no Pallas implementation)")
    return table[name](lr, **kw)
