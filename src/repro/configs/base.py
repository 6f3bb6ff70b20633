"""Architecture configuration system.

Every assigned architecture gets one module in this package defining a
``CONFIG = ArchConfig(...)`` with the exact published shape, plus the
``reduced()`` method used by CPU smoke tests (2 layers, d_model<=512,
<=4 experts).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (global, fixed).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """A single architecture configuration.

    ``family`` selects the block builder in ``repro.models.api``:
      dense | moe | hybrid | ssm | vlm | audio
    """

    name: str
    family: str
    source: str  # citation, e.g. "[arXiv:2407.21783]"

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    # Attention options -----------------------------------------------------
    head_dim: Optional[int] = None  # default d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    # Sliding window used by the long_500k decode variant (see DESIGN.md).
    long_context_window: int = 8_192

    # MLP -------------------------------------------------------------------
    mlp_type: str = "swiglu"  # swiglu | relu2 | gelu

    # MoE -------------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 0
    moe_impl: str = "grouped"  # grouped (no-drop) | densemask (oracle)

    # SSM / hybrid ----------------------------------------------------------
    ssm_state: int = 0
    d_conv: int = 4
    expand: int = 2
    chunk_size: int = 128
    attn_every: int = 6  # hybrid: shared attention block every k mamba layers

    # xLSTM -----------------------------------------------------------------
    slstm_every: int = 4  # one sLSTM block per this many layers

    # Modality frontends (stubs) --------------------------------------------
    n_frames: int = 0   # audio: precomputed frame embeddings per example
    n_patches: int = 0  # vlm: precomputed patch embeddings per example
    n_encoder_layers: int = 0  # enc-dec (whisper)

    # Numerics / training ---------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    embed_impl: str = "onehot"  # onehot (baseline) | gather (§Perf)
    attn_impl: str = "blocked"  # blocked (pure-JAX) | pallas (TPU kernel)
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # ----------------------------------------------------------------- props
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return round_up(self.vocab_size, 256)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def n_params(self) -> int:
        """Approximate parameter count (used for 6ND model FLOPs)."""
        d, hd = self.d_model, self.resolved_head_dim
        attn = d * (self.n_heads * hd) * 2 + d * (self.n_kv_heads * hd) * 2
        if self.mlp_type == "swiglu":
            mlp = 3 * d * self.d_ff
        else:
            mlp = 2 * d * self.d_ff
        if self.is_moe:
            mlp_all = self.n_experts * mlp + d * self.n_experts  # + router
        else:
            mlp_all = mlp
        per_layer = attn + mlp_all
        if self.family == "ssm":
            per_layer = self._xlstm_layer_params()
        elif self.family == "hybrid":
            per_layer = self._mamba_layer_params()
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb

    def n_active_params(self) -> int:
        """Active params per token (MoE: top_k experts only)."""
        if not self.is_moe:
            return self.n_params()
        d = self.d_model
        hd = self.resolved_head_dim
        attn = d * (self.n_heads * hd) * 2 + d * (self.n_kv_heads * hd) * 2
        per_exp = (3 if self.mlp_type == "swiglu" else 2) * d * self.d_ff
        per_layer = attn + self.top_k * per_exp + d * self.n_experts
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb

    def _mamba_layer_params(self) -> int:
        d, di, n = self.d_model, self.d_inner, self.ssm_state
        return d * 2 * di + di * self.d_conv + di * (2 * n + 2) + di * d

    def _xlstm_layer_params(self) -> int:
        d = self.d_model
        di = self.expand * d
        return 2 * d * di + 4 * di + di * d  # rough: proj up/gates/down

    # ------------------------------------------------------------- reduction
    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: 2 layers, d_model<=512, <=4 experts."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = min(self.n_kv_heads, max(1, n_heads // 2))
        head_dim = d_model // n_heads
        n_experts = min(self.n_experts, 4) if self.is_moe else 0
        top_k = min(self.top_k, 2) if self.is_moe else 0
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=2,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 1024),
            n_experts=n_experts,
            top_k=top_k,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            chunk_size=8,
            attn_every=2,
            slstm_every=2,
            n_frames=min(self.n_frames, 16) if self.n_frames else 0,
            n_patches=min(self.n_patches, 8) if self.n_patches else 0,
            n_encoder_layers=2 if self.n_encoder_layers else 0,
            long_context_window=64,
            dtype="float32",
        )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "phi3.5-moe-42b-a6.6b",
    "zamba2-7b",
    "internvl2-1b",
    "granite-moe-1b-a400m",
    "whisper-base",
    "llama3-405b",
    "qwen1.5-110b",
    "xlstm-1.3b",
    "qwen3-32b",
    "nemotron-4-15b",
]

_MODULE_FOR = {
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "zamba2-7b": "zamba2",
    "internvl2-1b": "internvl2",
    "granite-moe-1b-a400m": "granite_moe",
    "whisper-base": "whisper",
    "llama3-405b": "llama3_405b",
    "qwen1.5-110b": "qwen15_110b",
    "xlstm-1.3b": "xlstm",
    "qwen3-32b": "qwen3_32b",
    "nemotron-4-15b": "nemotron4_15b",
    # paper's own experiment models
    "paper-mlp": "paper",
    "paper-lenet": "paper",
}


def get_config(arch_id: str) -> ArchConfig:
    if arch_id not in _MODULE_FOR:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULE_FOR)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    if hasattr(mod, "CONFIGS"):
        return mod.CONFIGS[arch_id]
    return mod.CONFIG


def all_configs() -> dict:
    return {a: get_config(a) for a in ARCH_IDS}
