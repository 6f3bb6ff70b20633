"""``counts.py`` against hand counts at SmolLM2-135M's and the cut
granite-moe's shapes."""
import json
from pathlib import Path

import numpy as np
import pytest

import counts
from refs import decoder

CONF = Path(__file__).resolve().parents[1] / "configs"


def arch(name):
    return json.loads((CONF / f"{name}.json").read_text())["arch"]


def test_smollm2_params_by_hand():
    a = arch("smollm2-135m")
    d, f, V, L = 576, 1536, 49152, 30
    attn = 2 * d * (9 * 64) + 2 * d * (3 * 64)   # q, o + GQA k, v
    per_layer = attn + 3 * d * f                  # + SwiGLU
    assert counts.layer_matmul_params(a) == per_layer == 3_538_944
    # the tied table is the output head (counted) and the input lookup
    assert counts.matmul_params(a) == L * per_layer + d * V == 134_479_872
    total = sum(int(np.prod(s)) for s, _ in decoder.param_specs(a).values())
    # held once, plus the norm scales: the model's 134,515,008
    assert total == 134_479_872 + (2 * L + 1) * d == 134_515_008


def test_smollm2_train_flops_by_hand():
    a = arch("smollm2-135m")
    attn_pair = 4 * 9 * 64 * 30                   # q.k and p.v, all layers
    assert counts.attn_pair_flops(a) == attn_pair == 69_120
    want = 6 * 134_479_872 + 3 * attn_pair * 513 / 2
    assert counts.train_flops_per_token(a, 512) == pytest.approx(want)
    assert want == pytest.approx(860_067_072)


def test_granite_cut_params_by_hand():
    a = arch("granite-moe-1b-a400m")
    d, f, E, k = 1024, 512, 32, 8
    attn = 2 * d * (16 * 64) + 2 * d * (8 * 64)
    assert attn == 3_145_728
    per_layer = attn + k * 3 * d * f + d * E     # top-8 experts + router
    assert counts.layer_matmul_params(a) == per_layer == 15_761_408
    assert counts.matmul_params(a) == 2 * per_layer + d * 49155 \
        == 81_857_536
    # held: every expert, the padded tied table, the norms
    total = sum(int(np.prod(s)) for s, _ in decoder.param_specs(a).values())
    held_layer = attn + E * 3 * d * f + d * E + 2 * d
    assert total == 2 * held_layer + 49408 * d + d


def test_decode_counts_by_hand():
    a = arch("smollm2-135m")
    ctx = [100, 1]
    kv = 2 * 3 * 64 * 4                          # K and V rows, float32
    qo = 2 * 9 * 64 * 4                          # query and output
    assert counts.decode_attn_bytes(a, ctx) == 30 * (101 * kv + 2 * qo)
    assert counts.decode_attn_flops(a, ctx) == 69_120 * 101
    assert counts.decode_flops(a, ctx) == 2 * (2 * 134_479_872) \
        + 69_120 * 101


def test_momentum_bytes():
    assert counts.momentum_update_bytes(4 * 134_515_008) == \
        20 * 4 * 134_515_008
