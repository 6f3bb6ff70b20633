"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached, at paper-lenet's real shapes.

Interpret mode (every other kernel test) cannot see the TPU compiler's
refusals: block shapes off the (8, 128) tiling, too much VMEM, a kernel
that does not lower. Each test here lowers the compiled (interpret=False)
kernel for one chip of a described ``v5e:2x2`` topology and asserts the
Pallas call survives as a ``tpu_custom_call``. Nothing runs, so nothing
here says anything about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import decode_attention as da
from repro.kernels import exchange_epilogue as ee
from repro.kernels import fused_adamw, fused_momentum, fused_sgd, quantize
from repro.kernels import sq_norm
from repro.models import build_model
from repro.optim import packing

G = 4          # local-SGD groups of the chip smoke run
CHUNK = 256    # int8 codec chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def n_params():
    """paper-lenet's packed buffer length (shapes only, no allocation)."""
    model = build_model(get_config("paper-lenet"), schedule="rect")
    return packing.layout_of(model.abstract()).size


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo):
    assert 'custom_call_target="tpu_custom_call"' in hlo


@pytest.mark.parametrize("name", ["sgd", "momentum", "adamw"])
def test_fused_update_compiles(one_chip, n_params, name):
    """The packed optimizers hand the kernel the (G, N) buffer as is."""
    flat = ((G, n_params), jnp.float32)
    if name == "sgd":
        hlo = _compile(lambda p, g: fused_sgd.fused_sgd(
            p, g, lr=0.05, interpret=False), one_chip, flat, flat)
    elif name == "momentum":
        hlo = _compile(lambda p, g, m: fused_momentum.fused_momentum(
            p, g, m, lr=0.05, beta=0.9, interpret=False),
            one_chip, flat, flat, flat)
    else:
        hlo = _compile(lambda p, g, m, v: fused_adamw.fused_adamw(
            p, g, m, v, count=1, lr=1e-3, interpret=False),
            one_chip, flat, flat, flat, flat)
    _assert_kernel(hlo)


def test_sq_norm_groups_compiles(one_chip, n_params):
    _assert_kernel(_compile(
        lambda x: sq_norm.sq_norm_groups(x, interpret=False), one_chip,
        ((G, n_params), jnp.float32)))


@pytest.mark.parametrize("name", ["qdq", "quantize", "dequantize"])
def test_int8_codec_kernels_compile(one_chip, n_params, name):
    rows = (G * n_params) // CHUNK
    f32 = ((rows, CHUNK), jnp.float32)
    if name == "qdq":
        hlo = _compile(lambda x, u: ee.qdq_int8(x, u, interpret=False),
                       one_chip, f32, f32)
    elif name == "quantize":
        hlo = _compile(lambda x, u: quantize.quantize_int8(
            x, u, interpret=False), one_chip, f32, f32)
    else:
        hlo = _compile(lambda q, s: quantize.dequantize_int8(
            q, s, interpret=False), one_chip, ((rows, CHUNK), jnp.int8),
            ((rows, 1), jnp.float32))
    _assert_kernel(hlo)


def test_codec_mix_int8_compiles(one_chip, n_params):
    buf = ((G, n_params), jnp.float32)
    noise = ((1, G, n_params // CHUNK, CHUNK), jnp.float32)
    _assert_kernel(_compile(
        lambda x, x0, u: ee.codec_mix(x, x0, kind="int8", u=u, chunk=CHUNK,
                                      impl="pallas", interpret=False)[0],
        one_chip, buf, buf, noise))


@pytest.mark.parametrize("arch,page_size", [("paper-lenet", 16),
                                            ("qwen3-32b", 16)])
def test_paged_decode_attention_compiles(one_chip, arch, page_size):
    """paper-lenet (12 KV heads, hd 64, no GQA) and qwen3-32b (8 KV
    heads, hd 128, 8 query heads per KV head), pool rows sized by the
    serve engine's own geometry."""
    from repro.serve.paging import make_geom

    cfg = get_config(arch)
    slots, nblk = 4, 8
    geom = make_geom(page_size=page_size, n_kv=cfg.n_kv_heads,
                     head_dim=cfg.resolved_head_dim, n_layers_kv=1,
                     max_len=page_size * nblk, state_size=0, n_slots=slots)
    i32 = jnp.int32
    hlo = _compile(
        lambda q, pool, rk, rv, ln: da.paged_decode_attention(
            q, pool, rk, rv, ln, page_size=page_size, n_kv=cfg.n_kv_heads,
            interpret=False),
        one_chip,
        ((slots, cfg.n_heads, cfg.resolved_head_dim), jnp.float32),
        ((geom.n_pages, geom.page_elems), jnp.float32),
        ((slots, nblk), i32), ((slots, nblk), i32), ((slots,), i32))
    _assert_kernel(hlo)


def test_grouped_expert_products_compile(one_chip, monkeypatch):
    """The expert layer's megablox ``gmm``/``tgmm`` at granite-train-g2t4's
    shapes: two groups vmapped (their grouped products folded into one
    over 2 x 32 experts), 2,048 tokens a group (2 x 1,024) at top-8, d 1024,
    f 512; the three products forward and backward."""
    import repro.kernels
    from repro.models import moe

    monkeypatch.setattr(repro.kernels, "use_interpret", lambda: False)
    monkeypatch.setattr(moe, "use_interpret", lambda: False)
    Gr, E, D, F, M = 2, 32, 1024, 512, 2 * 1024 * 8

    def experts(wg, wu, wd, rows, sizes):
        def one(wg, wu, wd, rows, sizes):
            mm = lambda r, w: moe.grouped_matmul(r, w.astype(r.dtype), sizes)
            y = mm(jax.nn.silu(mm(rows, wg)) * mm(rows, wu), wd)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return jax.vmap(jax.grad(one, argnums=(0, 1, 2, 3)))(
            wg, wu, wd, rows, sizes)

    w_in, w_out = ((Gr, E, D, F), jnp.float32), ((Gr, E, F, D), jnp.float32)
    hlo = _compile(experts, one_chip, w_in, w_in, w_out,
                   ((Gr, M, D), jnp.bfloat16), ((Gr, E), jnp.int32))
    _assert_kernel(hlo)
    assert "tgmm" in hlo and "gmm" in hlo
