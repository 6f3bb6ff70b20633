# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax as _jax


def use_interpret() -> bool:
    """Single decision point for kernel dispatch: Pallas interpret mode
    everywhere except a real TPU backend (compiled VMEM kernels)."""
    return _jax.default_backend() != "tpu"


def pallas_supported() -> bool:
    """Backends the Pallas kernels actually execute on: compiled VMEM
    kernels on TPU, interpret-mode (kernel-body validation) on CPU. Other
    backends (e.g. an untested GPU lowering) must REFUSE an explicit
    impl="pallas" rather than silently running something else."""
    return _jax.default_backend() in ("tpu", "cpu")


def resolve_impl(impl: str) -> str:
    """Shared impl="auto" resolution for everything that fronts a Pallas
    kernel with a jnp fallback (packed optimizers, comm codecs): "jnp"
    everywhere except a real TPU backend. An EXPLICIT impl="pallas" on a
    backend the kernels don't support raises instead of silently falling
    back to jnp — callers asked for the kernels, not an approximation."""
    if impl == "auto":
        return "jnp" if use_interpret() else "pallas"
    if impl not in ("pallas", "jnp"):
        raise ValueError(
            f"unknown impl {impl!r} (have 'auto', 'jnp', 'pallas')")
    if impl == "pallas" and not pallas_supported():
        raise NotImplementedError(
            f"impl='pallas' requested on backend "
            f"{_jax.default_backend()!r}: the fused/quantize kernels "
            "compile on TPU and run in interpret mode on CPU only — pass "
            "impl='jnp' (same math, one XLA fusion) or impl='auto'")
    return impl


def named_pallas_call(name: str, kernel, **kwargs):
    """``pl.pallas_call`` under a stable name: the kernel is built with
    ``name=name`` and each call runs inside ``jax.named_scope(name)``, so
    the device trace finds the kernel by its name (its op's ``tf_op``
    ends in ``<name>/pallas_call``) and not by its operand shapes."""
    from jax.experimental import pallas as pl
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args):
        with _jax.named_scope(name):
            return call(*args)

    return run


def as_rows(x):
    """A packed buffer as the 2-D (rows, N) view the flat-buffer kernels
    take: a 1-D buffer is one row, the (G, N) grouped one is itself."""
    return x.reshape(-1, x.shape[-1])


def flat_blocks(shape, block: int):
    """Shared blocking for the element-wise flat-buffer kernels over a
    (rows, N) buffer: blocks of every row by ``cols`` columns — a
    multiple of 128 holding about ``block`` elements, or all of N — and a
    grid covering N. Whole rows and 128-multiple columns satisfy the
    TPU's (8, 128) block rule for any row count. A partial last block
    needs no padding: an element-wise update never mixes the don't-care
    tail into real elements and writes past N are dropped, so no operand
    is ever copied.

    Returns (block_shape, grid)."""
    rows, n = shape
    cols = min(max(128, block // rows // 128 * 128), n)
    return (rows, cols), (-(-n // cols),)
