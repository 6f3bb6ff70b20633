"""Operations and bytes that the work requires, from shapes alone.

Conventions (what "required" means here):

* Matrix parameters N: every projection a token passes through — the
  attention's q, k, v and output projections, the feed-forward's three
  SwiGLU matrices (of the ``top_k`` experts a token is routed to, plus the
  router), and the output head over the published vocabulary. The input
  token table is a lookup and counts for nothing; norms count for
  nothing.
* Training: 6 * N per token (forward 2N, backward 4N), plus attention's
  score and value products over the causal context: 4 * H * hd per
  (query, key) pair in the forward pass, (S + 1) / 2 keys per query on
  average, times 3 for forward and backward. Recomputed operations and
  computed-then-masked ones (all experts under a dense mask, the upper
  triangle of a rectangular attention schedule) count for nothing.
* Decode: 2 * N per active slot and step, plus 4 * H * hd per resident
  token of its context in each layer.
"""
from __future__ import annotations


def head_dim(arch: dict) -> int:
    return arch.get("head_dim") or arch["d_model"] // arch["n_heads"]


def layer_matmul_params(arch: dict) -> int:
    d, H, KV, hd = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                    head_dim(arch))
    attn = d * H * hd * 2 + d * KV * hd * 2
    ffn = 3 * d * arch["d_ff"]
    if arch.get("n_experts"):
        return attn + arch["top_k"] * ffn + d * arch["n_experts"]
    return attn + ffn


def matmul_params(arch: dict) -> int:
    """N: matrix parameters a token passes through (see module doc)."""
    return (arch["n_layers"] * layer_matmul_params(arch)
            + arch["d_model"] * arch["vocab_size"])


def attn_pair_flops(arch: dict) -> int:
    """Forward score + value operations for one (query, key) pair, all
    layers."""
    return 4 * arch["n_heads"] * head_dim(arch) * arch["n_layers"]


def train_flops_per_token(arch: dict, seq: int) -> float:
    return (6 * matmul_params(arch)
            + 3 * attn_pair_flops(arch) * (seq + 1) / 2)


def decode_flops(arch: dict, contexts) -> float:
    """One decode step over slots whose resident contexts (tokens
    attended, the new one included) are ``contexts``."""
    n = 2 * matmul_params(arch)
    return sum(n + attn_pair_flops(arch) * c for c in contexts)


def decode_attn_bytes(arch: dict, contexts, kv_bytes: int = 4,
                      act_bytes: int = 4) -> int:
    """Bytes one decode step's attention must move over all layers: the
    K and V rows of every resident token, and each slot's query and
    output."""
    KV, H, hd, L = (arch["n_kv_heads"], arch["n_heads"], head_dim(arch),
                    arch["n_layers"])
    per = sum(2 * c * KV * hd * kv_bytes + 2 * H * hd * act_bytes
              for c in contexts)
    return L * per


def decode_attn_flops(arch: dict, contexts) -> float:
    return sum(attn_pair_flops(arch) * c for c in contexts)


def momentum_update_bytes(n_elems: int) -> int:
    """Heavy-ball update of float32 buffers: read p, mu, g; write p, mu."""
    return 20 * n_elems
