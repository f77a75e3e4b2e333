"""The hybrid model's arithmetic against numbers worked by hand for
Phi-4-mini-flash-reasoning (ISSUE 30): widths 2560 / 10240 / 40 x 64,
d_inner 5120, state 16, dt_rank 160, conv 4; 915,311,616 parameters in the
eight-layer cut with an eighth of the vocabulary, 3,852,562,944 in the
published 32 layers."""

import json
import os

import pytest

from benchmark import arith_hybrid as ah

HERE = os.path.dirname(os.path.abspath(__file__))
FULL_KINDS = (["mamba", "window"] * 8 + ["mamba", "full"]
              + ["gmu", "cross"] * 7)


def _model():
    with open(os.path.join(HERE, "..", "configs",
                           "phi4-mini-flash-train-d8.json")) as f:
        return json.load(f)["model"]


def test_matmul_parameters_by_kind_by_hand():
    got = ah.mixer_matmul_params(_model())
    # mamba: in 2560 x 10240, x 5120 x (160 + 32), dt 160 x 5120, out 5120 x 2560
    assert got["mamba"] == 26_214_400 + 983_040 + 819_200 + 13_107_200 \
        == 41_123_840
    # attention: qkv 2560 x (40 + 20 + 20) x 64, out 2560 x 2560
    assert got["window"] == got["full"] == 13_107_200 + 6_553_600 == 19_660_800
    assert got["gmu"] == 2 * 2560 * 5120 == 26_214_400
    assert got["cross"] == 2 * 2560 * 2560 == 13_107_200


def test_param_count_by_hand():
    m = _model()
    mlp = 3 * 2560 * 10240 + 4 * 2560           # + the layer's two LayerNorms
    assert mlp == 78_653_440
    # what is in no matmul: conv 4 x 5120 + bias, dt bias, A 5120 x 16, D;
    # qkv bias 5120 (q bias 2560), out bias 2560, 4 lambda vectors, norm 128
    mamba = 41_123_840 + 20_480 + 5_120 + 5_120 + 81_920 + 5_120
    attn = 19_660_800 + 5_120 + 2_560 + 256 + 128
    cross = 13_107_200 + 2_560 + 2_560 + 256 + 128
    embed = 25_008 * 2560
    total = (embed + 2 * 2560 + 3 * mamba + 3 * attn + 26_214_400 + cross
             + 8 * mlp)
    assert ah.param_count(m) == total == 915_311_616
    published = dict(m, vocab_size=200_064, layer_kinds=FULL_KINDS)
    assert ah.param_count(published) == 3_852_562_944   # the card's 3.8 B


def test_visible_pairs_under_window_full_and_cross():
    assert ah.visible_pairs(4) == 10                        # 1 + 2 + 3 + 4
    assert ah.visible_pairs(4, window=2) == 1 + 2 + 2 + 2
    assert ah.visible_pairs(4, window=9) == 10              # reaches no edge
    assert ah.visible_pairs(8192) == 33_558_528
    assert ah.visible_pairs(8192, 512) == 131_328 + 7680 * 512 == 4_063_488
    # a layer's forward: 40 heads x 6 x 64 a visible pair
    assert ah.attention_fwd_flops(1, 40, 64, 8192) == 15_360 * 33_558_528
    assert ah.attention_fwd_flops(1, 40, 64, 8192, 512) \
        == pytest.approx(6.2415e10, rel=1e-4)


def test_train_flops_per_token_by_hand():
    m = _model()
    matmul = 6 * (25_008 * 2560 + 3 * 41_123_840 + 3 * 19_660_800
                  + 26_214_400 + 13_107_200 + 8 * 3 * 2560 * 10240)
    assert matmul == 5_489_049_600
    full = 3 * 15_360 * 33_558_528 / 8192       # a full or cross layer
    window = 3 * 15_360 * 4_063_488 / 8192
    scan = 3 * 7 * 5120 * 16 * 3                 # three mamba layers
    got = ah.train_flops_per_token(m, 8192)
    assert got == pytest.approx(matmul + 2 * full + 2 * window + scan)
    assert got == pytest.approx(5.9175e9, rel=1e-4)
    # the attention a token pays grows with the sequence, the rest not
    assert ah.train_flops_per_token(m, 4096) < got


def test_scan_min_bytes_by_hand():
    # x bf16 + dt f32 + y bf16 over 8192 x 5120; B, C f32 over 8192 x 16;
    # A 5120 x 16 and D 5120 f32
    want = 8192 * 5120 * 8 + 2 * 8192 * 16 * 4 + 5120 * 16 * 4 + 5120 * 4
    assert ah.scan_min_bytes(1, 8192, _model()) == want == 336_941_056
