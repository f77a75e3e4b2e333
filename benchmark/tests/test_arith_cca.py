"""The compressed-convolutional-attention, top-1-expert model's arithmetic
against numbers worked by hand for ZAYA1-8B (ISSUE 44): hidden 2048; 8 query
/ 2 KV heads of 128, so latents of 1024 and 256; convolutions of 2 + 2 taps;
a router of 256 over 16 experts; experts 2048 wide, one a token, all sixteen
held; 897,477,704 parameters in the four-layer cut with an eighth of the
tied vocabulary."""

import json
import os

from benchmark import arith_cca as ac

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "zaya1-8b-train-d4.json"


def _model():
    with open(os.path.join(HERE, "..", "configs", NAME)) as f:
        return json.load(f)["model"]


def test_parameters_by_part_by_hand():
    p = ac.params_by_part(_model())
    # W_q and W_o 2048 x 1024 each; W_k 2048 x 256; W_v1 + W_v2 2048 x 256
    assert p["attention_matmul"] == 2 * 2_097_152 + 2 * 524_288 == 5_242_880
    # two taps and a bias a channel, 1280 channels
    assert p["depthwise_conv"] == 3 * 1280 == 3_840
    # ten heads x two taps x 128 x 128, and a bias a channel
    assert p["head_mix_matmul"] == 10 * 2 * 16_384 == 327_680
    assert p["head_mix_conv"] == 327_680 + 1_280 == 328_960
    assert p["tau"] == 2
    assert p["router_matmul"] == 524_288 + 2 * 65_536 + 4_096 == 659_456
    # + three biases, alpha and the norm of 256 each, beta of 16
    assert p["router"] == 659_456 + 5 * 256 + 16 == 660_752
    assert p["one_expert"] == 3 * 2048 * 2048 == 12_582_912
    assert p["norms"] == 4_096 and p["residual_scales"] == 16_384
    assert p["embedding_and_head"] == 32_784 * 2048 == 67_141_632   # tied


def test_param_count_by_hand():
    m = _model()
    assert ac.layer_params(m) == 16 * 12_582_912 + 5_242_880 + 3_840 \
        + 328_960 + 2 + 660_752 + 4_096 + 16_384 == 207_583_506
    assert ac.param_count(m) == 4 * 207_583_506 + 67_141_632 + 2_048 \
        == 897_477_704
    # 14 B a parameter of train state: 11.70 GiB of the chip's 15.75
    assert round(ac.param_count(m) * 14 / 2 ** 30, 2) == 11.70
    # the published model: 40 layers, the whole tied vocabulary
    whole = {**m, "num_hidden_layers": 40, "vocab_size": 262_272}
    assert ac.param_count(whole) == 40 * 207_583_506 + 262_272 * 2048 + 2048
    assert 8.8e9 < ac.param_count(whole) < 8.9e9
    # active a token: one expert of sixteen
    active = ac.param_count(whole) - 40 * 15 * 12_582_912
    assert 1.28e9 < active < 1.30e9     # 0.76 B without the 0.54 B table


def test_a_layers_forward_by_part_is_the_cells_why():
    m = _model()
    part = ac.layer_fwd_flops_per_token(m, 8192)
    mega = {k: round(v / 1e6, 2) for k, v in part.items()}
    assert mega == {"projections": 10.49, "head_mix_conv": 0.66,
                    "triangle": 16.78, "router": 1.32, "experts": 25.17}
    assert round(sum(part.values()) / 1e6, 1) == 54.4
    mixer = part["projections"] + part["head_mix_conv"] + part["triangle"]
    assert round(mixer / 1e6, 1) == 27.9
    assert round((part["router"] + part["experts"]) / 1e6, 1) == 26.5
    # every expert held: a token's one row is here whatever the routing
    assert ac.expected_rows_per_token(m) == 1.0
    assert ac.expected_rows_per_token({**m, "num_experts": 8}) == 0.5


def test_train_flops_per_token_by_hand():
    m = _model()
    layer = 2 * (5_242_880 + 327_680 + 659_456 + 12_582_912) \
        + 4 * 128 * 8 * 8193 / 2
    head = 2 * 32_784 * 2048
    assert ac.train_flops_per_token(m, 8192) == 3 * (4 * layer + head)
    # 8.65 TFLOP a row of 8192 trained
    assert round(ac.train_flops_per_token(m, 8192) * 8192 / 1e12, 2) == 8.65
    # the head's share of the forward: 38 % here, 33 % in the 40-layer model
    assert round(head / (4 * layer + head), 2) == 0.38
    whole = 2 * 262_272 * 2048
    assert round(whole / (40 * layer + whole), 2) == 0.33
    # half the rows, half the experts' operations
    less = ac.train_flops_per_token(m, 8192, rows_per_token=0.5)
    assert ac.train_flops_per_token(m, 8192) - less \
        == 3 * 4 * 12_582_912
    assert ac.attention_fwd_flops(2, m, 8192) == 2 * 4 * 128 * 8 \
        * 8192 * 8193 / 2
