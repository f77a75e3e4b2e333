"""The hyper-connected latent-attention model's arithmetic against numbers
worked by hand for Xing4.0-29B-A4B (ISSUE 52): hidden 3584, 32 heads of 192 /
128, a query latent of 768, latent 512 + rope 64, dense 9216, experts 1024
wide, 4 of 64 a token, 1 shared, four lanes, one prediction block;
759,403,795 parameters in the five-layer cut that holds 8 experts a layer and
an eighth of the vocabulary, 913,588,366 with the block, which the cell
leaves to the last stage's chip."""

import json
import os

import pytest

from benchmark import arith_hc_moe as ah, arith_moe

HERE = os.path.dirname(os.path.abspath(__file__))


def _model():
    with open(os.path.join(HERE, "..", "configs",
                           "xing4.0-29b-a4b-train-d5e8.json")) as f:
        return json.load(f)["model"]


def test_parameters_by_kind_by_hand():
    got = ah.params_by_kind(_model())
    # W_qa 3584 x 768, its norm 768, W_qb 768 x 32 x 192, W_kva 3584 x 576,
    # the latent norm 512, W_kvb 512 x 32 x 256, W_o 4096 x 3584
    assert got["attention"] == (2_752_512 + 768 + 4_718_592 + 2_064_384 + 512
                                + 4_194_304 + 14_680_064) == 28_411_136
    # a sublayer's lanes: w_hc 14336 x 24, scale 3, base 24; two a layer
    assert got["lanes"] == 2 * (344_064 + 3 + 24) == 688_182
    assert got["collapse"] == 14_336 * 4 + 1 + 4 == 57_349
    assert got["dense_ffn"] == 3 * 3584 * 9216 == 99_090_432
    assert got["router"] == 3584 * 64 + 64 == 229_440
    assert got["shared_expert"] == got["one_expert"] == 3 * 3584 * 1024 \
        == 11_010_048
    assert got["embedding_and_head"] == 2 * 16_384 * 3584 == 117_440_512
    assert got["mtp_in"] == 2 * 3584 * 3584 + 2 * 3584 == 25_697_280


def _with_block():
    return {**_model(), "num_nextn_predict_layers": 1}


def test_param_count_by_hand():
    m = _with_block()
    outside = 28_411_136 + 7168 + 688_182   # attention, two norms, lanes
    dense = outside + 99_090_432
    expert = outside + 229_440 + 11_010_048 + 8 * 11_010_048
    assert dense == 128_196_918 and expert == 128_426_358
    behind = 3584 + 57_349                  # a final norm and a collapse
    stack = 117_440_512 + behind + dense + 4 * expert
    assert stack == 759_403_795             # without the block: 9.90 GiB
    assert ah.param_count(_model()) == stack       # the cell's
    assert ah.param_count(m) == stack + 25_697_280 + expert + behind \
        == 913_588_366                      # 11.91 GiB at 14 B
    assert 913_588_366 * 14 / 2 ** 30 == pytest.approx(11.912, abs=1e-3)
    # the published model: two dense layers, 38 expert layers of 64 experts
    full = {**m, "num_hidden_layers": 40, "first_k_dense_replace": 2,
            "n_routed_experts": 64, "vocab_size": 131_072}
    assert 28e9 < ah.param_count(full) < 31e9          # the card's 29 B
    # one lane, no query latent, no block: arith_moe's own count
    plain = {**m, "hc_mult": None, "q_lora_rank": None,
             "num_nextn_predict_layers": 0}
    assert ah.param_count(plain) == arith_moe.param_count(plain)


def test_train_flops_per_token_by_hand():
    m = _with_block()
    assert arith_moe.expected_rows_per_token(m) == 4 * 8 / 64 == 0.5
    # matrices a token meets in one layer's attention: less the two norms
    attention = 28_411_136 - 512 - 768
    lanes = 2 * 14_336 * 24
    assert (attention, lanes) == (28_409_856, 688_128)
    # the triangle at 4096: 2 x 320 x 32 heads x 2048.5 pairs a query
    triangle = 2 * 320 * 32 * 4097 / 2
    assert triangle == 41_953_280
    expert = attention + lanes + 3584 * 64 + 11_010_048 + 0.5 * 11_010_048
    dense = attention + lanes + 99_090_432
    head, collapse = 16_384 * 3584, 14_336 * 4
    weights = dense + 5 * expert + 2 * (head + collapse) + 2 * 3584 * 3584
    want = 6 * weights + 6 * 3 * triangle
    assert ah.train_flops_per_token(m, 4096) == pytest.approx(want, rel=1e-12)
    assert 3.5e9 < want < 3.9e9
    # measured rows replace the expectation; the block's layer counts them
    more = ah.train_flops_per_token(m, 4096, rows_per_token=1.0)
    assert more - want == pytest.approx(6 * 5 * 0.5 * 11_010_048)
    # without the block: its layer, W_eh, its collapse and the second head go
    less = ah.train_flops_per_token(_model(), 4096)
    assert want - less == pytest.approx(
        6 * (expert + head + collapse + 2 * 3584 * 3584) + 3 * triangle)


def test_the_lanes_kernels_least_bytes_by_hand():
    m = _model()
    # a token: the stream 4 x 3584 bfloat16 in, u out, 24 float32 numbers
    # out; the float32 leaf 14336 x 24 once
    assert ah.hc_pre_fwd_min_bytes(4096, m) == 4096 * (
        2 * 14_336 + 2 * 3584 + 96) + 4 * 344_064 == 148_570_112
    # the stream in and out, y in, 4 + 16 float32 numbers in
    assert ah.hc_post_fwd_min_bytes(4096, m) == 4096 * (
        4 * 14_336 + 2 * 3584 + 80) == 264_568_832
    # at the v5e's 819 GB/s: 0.18 and 0.32 ms a call
    assert ah.hc_pre_fwd_min_bytes(4096, m) / 819e9 == pytest.approx(
        0.1814e-3, rel=1e-3)
