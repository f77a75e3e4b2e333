"""The Mamba-2 / attention / routed-expert model's arithmetic against numbers
worked by hand for Nemotron-3-Nano-30B-A3B (ISSUE 48): hidden 2688; 64 heads
of 64 over 8 groups with a 128-wide state, 4 taps; 32 query / 2 KV heads of
128; experts 1856 wide and UNGATED, six a token of 128, 8 held, a shared one
of 3712; 666,963,456 parameters in the nine-layer cut with an eighth of the
untied vocabulary; and against a hand count at a tiny size."""

import json
import os

from benchmark import arith_ssd as a

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "nemotron-3-nano-30b-a3b-train-d9e8.json"
TINY = {"hybrid_override_pattern": "ME*", "hidden_size": 8,
        "mamba_num_heads": 4, "mamba_head_dim": 2, "n_groups": 2,
        "ssm_state_size": 3, "conv_kernel": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "moe_intermediate_size": 5,
        "moe_shared_expert_intermediate_size": 6, "n_routed_experts": 2,
        "router_width": 8, "num_experts_per_tok": 2, "vocab_size": 16}


def _model():
    with open(os.path.join(HERE, "..", "configs", NAME)) as f:
        return json.load(f)["model"]


def test_parameters_by_part_by_hand():
    p = a.params_by_part(_model())
    # W_in 2688 x (4096 + 6144 + 64), W_out 4096 x 2688
    assert p["mamba_matmul"] == 27_697_152 + 11_010_048 == 38_707_200
    # + the conv 6144 x 4 + 6144, A_log, D, dt_bias 64 each, the gated norm
    # 4096, the pre-norm 2688
    assert p["mamba_layer"] == 38_707_200 + 30_720 + 192 + 4_096 + 2_688 \
        == 38_744_896
    assert p["full_matmul"] == 2 * 11_010_048 + 2 * 688_128 == 23_396_352
    assert p["full_layer"] == 23_399_040
    assert p["router"] == 344_064
    assert p["shared_expert"] == 2 * 2688 * 3712 == 19_955_712
    assert p["one_expert"] == 2 * 2688 * 1856 == 9_977_856      # TWO matrices
    assert p["embedding_and_head"] == 2 * 16_384 * 2688 == 88_080_384


def test_param_count_to_the_unit():
    m = _model()
    # the router, its bias, the shared expert, the norm; 8 held experts
    assert a.expert_layer_params(m) == 20_302_592 + 8 * 9_977_856
    assert a.param_count(m) == 4 * 38_744_896 + 23_399_040 \
        + 4 * (20_302_592 + 8 * 9_977_856) + 88_080_384 + 2_688 \
        == 666_963_456
    assert round(a.param_count(m) * 14 / 2 ** 30, 2) == 8.70
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    whole = {**m, "hybrid_override_pattern": pattern,
             "num_hidden_layers": 52, "n_routed_experts": 128,
             "vocab_size": 131_072}
    assert round(a.param_count(whole) / 1e9, 1) == 31.6
    active = a.param_count(whole) - 23 * 122 * 9_977_856
    assert 3.5e9 < active < 3.7e9       # 3.2 B without the 0.35 B table


def test_the_tiny_size_by_hand():
    # mamba: d_inner 8, conv channels 8 + 2 x 2 x 3 = 20; W_in 8 x (8 + 20 +
    # 4) = 256, W_out 64; conv 5 x 20, three vectors of 4, norms 8 + 8
    p = a.params_by_part(TINY)
    assert p["mamba_matmul"] == 256 + 64
    assert p["mamba_layer"] == 320 + 100 + 12 + 8 + 8 == 448
    assert p["full_layer"] == 2 * 8 * 8 + 2 * 8 * 4 + 8 == 200
    assert a.expert_layer_params(TINY) == 64 + 8 + 2 * 8 * 6 + 8 \
        + 2 * (2 * 8 * 5) == 336
    assert a.param_count(TINY) == 448 + 200 + 336 + 2 * 16 * 8 + 8 == 1248
    assert a.expected_rows_per_token(TINY) == 2 * 2 / 8
    # a head a chunk of 128: C B^T 2 x 128^2 x 3 once a group of 2 heads;
    # the masked product 2 x 128^2 x 2; the state's two 2 x 128 x 3 x 2
    assert a.scan_chunk_fwd_flops(TINY) == 49_152 + 65_536 + 3_072
    assert a.scan_fwd_flops(2, TINY, 200) == 2 * 4 * 2 * 117_760
    assert a.scan_min_bytes(1, TINY, 10) == 10 * (2 * (16 + 12) + 16)
    assert a.attention_fwd_flops(1, TINY, 10) == 4 * 4 * 2 * 55
    # a trained token at a sequence of 128: 6 x the weights it meets, the
    # routed rows, 3 x the triangle's and the recurrence's forward
    weights = 320 + (2 * 8 * 8 + 2 * 8 * 4) + (64 + 96) + 16 * 8
    assert a.train_flops_per_token(TINY, 128) == 6 * (weights + 0.5 * 80) \
        + 3 * 4 * 4 * 2 * 129 / 2 + 3 * 4 * 117_760 / 128


def test_the_cells_forward_by_part_is_the_cells_why():
    m = _model()
    p = a.params_by_part(m)
    mega = lambda v: round(v / 1e6, 1)
    assert mega(2 * p["mamba_matmul"]) == 77.4
    assert mega(a.scan_fwd_flops(1, m, 8192) / 8192) == 3.4
    assert mega(2 * p["shared_expert"]) == 39.9
    assert mega(2 * a.expected_rows_per_token(m) * p["one_expert"]) == 7.5
    assert mega(a.attention_fwd_flops(1, m, 8192) / 8192) == 67.1
    assert round(a.train_flops_per_token(m, 8192) / 1e9, 2) == 2.15
    # 164 operations a byte moved, under the chip's 240: the bytes bind
    assert 160 < a.scan_fwd_flops(1, m, 8192) / a.scan_min_bytes(
        1, m, 8192) < 170
