"""Operations and bytes that the grouped expert layer's matrix products
require, from shapes alone (the ``experts`` scope of ``models/moe.py``).

Per routed (token, expert) pair and layer, SwiGLU's three products
(gate and up, d x f; down, f x d) take 2 * 3 * d * f FLOPs forward and
twice that backward (the rows' and the weights' gradients): 18 * d * f.
The forward recomputed under remat counts for nothing.

Bytes, per layer and group: the held experts' weights read once in
bfloat16 (3 * E * d * f * 2), their gradients written once in float32
(3 * E * d * f * 4), and the routed rows in and out, bfloat16
(2 * pairs * d * 2). What the products keep between them (the gate's
and up's outputs) stays off the count.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def routed_pairs(arch: dict, tokens: int) -> int:
    """(token, expert) pairs of one layer over ``tokens`` tokens."""
    return tokens * arch["top_k"]


def expert_weights(arch: dict) -> int:
    """Elements of one layer's expert matrices, every expert held."""
    return 3 * arch["n_experts"] * arch["d_model"] * arch["d_ff"]


def grouped_mm_flops(arch: dict, tokens: int) -> float:
    """Forward and backward FLOPs of the grouped products, all layers,
    for ``tokens`` tokens of one group."""
    return (18.0 * arch["d_model"] * arch["d_ff"]
            * routed_pairs(arch, tokens) * arch["n_layers"])


def grouped_mm_bytes(arch: dict, tokens: int) -> float:
    """Bytes the grouped products must move, all layers, for ``tokens``
    tokens of one group (module doc)."""
    w = expert_weights(arch) * (BF16 + F32)
    rows = 2 * routed_pairs(arch, tokens) * arch["d_model"] * BF16
    return float(w + rows) * arch["n_layers"]


def grouped_mm_seconds(arch: dict, tokens: int, peaks: dict) -> float:
    """The least time the grouped products can take on one chip: the
    larger of FLOPs over the bf16 peak and bytes over HBM bandwidth."""
    return max(grouped_mm_flops(arch, tokens) / peaks["bf16_flops_per_s"],
               grouped_mm_bytes(arch, tokens) / peaks["hbm_bytes_per_s"])
