"""The windowed / full GQA, routed-expert model's arithmetic against numbers
worked by hand for Laguna-S-2.1 (ISSUE 36): hidden 3072, 8 KV heads of 128
under 48 (full) or 72 (sliding) query heads, window 512, dense 12288,
experts 1024 wide, 10 of 256 a token, one shared; 811,017,216 parameters in
the five-layer cut that holds 8 experts a layer and an eighth of the
vocabulary."""

import json
import os

import pytest

from benchmark import arith_moe, arith_swa_moe as am

HERE = os.path.dirname(os.path.abspath(__file__))
FULL, SLIDING = "full_attention", "sliding_attention"


def _model():
    with open(os.path.join(HERE, "..", "configs",
                           "laguna-s-2.1-train-d5e8.json")) as f:
        return json.load(f)["model"]


def test_parameters_by_kind_of_layer_by_hand():
    m = _model()
    # a full layer: W_q 3072 x 6144, W_k and W_v 3072 x 1024 each, the
    # gate 3072 x 48, W_o 6144 x 3072
    assert am.attention_params(m, 48) == (18_874_368 + 2 * 3_145_728
                                          + 147_456 + 18_874_368) \
        == 44_187_648
    # a sliding layer: 9216 wide where the full one is 6144, the gate 72
    assert am.attention_params(m, 72) == (28_311_552 + 6_291_456 + 221_184
                                          + 28_311_552) == 63_135_744
    got = am.params_by_kind(m)
    assert got["dense_ffn"] == 3 * 3072 * 12288 == 113_246_208
    assert got["router"] == 3072 * 256 == 786_432
    assert got["shared_expert"] == got["one_expert"] == 3 * 3072 * 1024 \
        == 9_437_184
    assert got["embedding_and_head"] == 2 * 12_544 * 3072 == 77_070_336
    assert am.heads_by_kind(m) == {FULL: [48, 48], SLIDING: [72, 72, 72]}
    assert am.expert_layers(m) == 4


def test_param_count_by_hand():
    m = _model()
    experts = 786_432 + 9_437_184 + 8 * 9_437_184
    assert experts == 85_721_088
    layer0 = 44_187_648 + 6144 + 113_246_208
    sliding = 63_135_744 + 6144 + experts
    layer4 = 44_187_648 + 6144 + experts
    assert (layer0, sliding, layer4) == (157_440_000, 148_862_976,
                                         129_914_880)
    assert am.param_count(m) == layer0 + 3 * sliding + layer4 \
        + 77_070_336 + 3072 == 811_017_216
    # the published model: 48 layers, 256 experts, the whole vocabulary
    full = {**m, "num_hidden_layers": 48, "num_experts": 256,
            "vocab_size": 100_352}
    assert 115e9 < am.param_count(full) < 121e9         # the card's 118 B


def test_visible_pairs_and_attention_flops_of_each_kind_by_hand():
    m = _model()
    # the triangle: 8192 x 8193 / 2; the window: 512 x 513 / 2 + 7680 x 512
    assert am.visible_pairs(8192) == 33_558_528
    assert am.visible_pairs(8192, 512) == 131_328 + 3_932_160 == 4_063_488
    assert am.visible_pairs(256, 512) == 256 * 257 / 2
    # a full layer: 4 x 128 a pair a head, 48 heads
    assert am.attention_fwd_flops(1, 48, 128, 8192) \
        == 512 * 48 * 33_558_528 == pytest.approx(8.2474e11, rel=1e-4)
    # a sliding layer: 72 heads over an eighth of the pairs
    assert am.attention_fwd_flops(1, 72, 128, 8192, 512) \
        == 512 * 72 * 4_063_488 == pytest.approx(1.4980e11, rel=1e-4)
    assert am.kind_fwd_flops(1, m, 8192, FULL) == 2 * 512 * 48 * 33_558_528
    assert am.kind_fwd_flops(1, m, 8192, SLIDING) \
        == 3 * 512 * 72 * 4_063_488


def test_train_flops_per_token_by_hand():
    m = _model()
    assert am.expected_rows_per_token(m) == 10 * 8 / 256 == 0.3125
    # forward operations a token: the attention matrices of 2 full and 3
    # sliding layers, the dense feed-forward, 4 x (router + shared + 0.3125
    # rows of an expert), the head
    attn_mats = 2 * (2 * 44_187_648 + 3 * 63_135_744)
    dense = 2 * 113_246_208
    sparse = 4 * 2 * (786_432 + 9_437_184 + 0.3125 * 9_437_184)
    head = 2 * 12_544 * 3072
    pairs = (2 * 512 * 48 * 33_558_528 + 3 * 512 * 72 * 4_063_488) / 8192
    forward = attn_mats + dense + sparse + head + pairs
    assert am.train_flops_per_token(m, 8192) == pytest.approx(3 * forward)
    assert 3 * forward == pytest.approx(3.6622e9, rel=1e-4)
    # the deployment's 32 data-parallel chips would send 10 rows a token
    more = am.train_flops_per_token(m, 8192, rows_per_token=10.0)
    assert more - 3 * forward == pytest.approx(
        6 * 4 * (10.0 - 0.3125) * 9_437_184)


def test_grouped_matmul_operations_and_least_bytes_at_the_cells_widths():
    sizes = [320.0] * 8
    assert arith_moe.grouped_matmul_flops(sizes, 3072, 1024) \
        == 2 * 2560 * 3072 * 1024 == pytest.approx(1.6106e10, rel=1e-4)
    # rows in and out in bfloat16, eight matrices of 3072 x 1024 once
    assert arith_moe.grouped_matmul_min_bytes(sizes, 3072, 1024) \
        == 2 * (2560 * 4096 + 8 * 3072 * 1024) == 71_303_168
    # at 320 rows a group the kernel is MEMORY-bound: the matrices are 50
    # MB of the 71 (87 us over the HBM peak against 82 us of compute)
    assert 71_303_168 / 819e9 > 1.6106e10 / 197e12
