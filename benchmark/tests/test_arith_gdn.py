"""The gated-delta-rule / gated-full-attention, routed-expert model's
arithmetic against numbers worked by hand for Qwen3-Next-80B-A3B (ISSUE 42):
hidden 2048; a linear layer of 16 key and 32 value heads of 128 with a conv of
4 taps; a full layer of 16 query / 2 KV heads of 256 with a doubled W_q;
experts 512 wide, 10 of 512 a token, one gated shared expert of 512;
625,667,136 parameters in the four-layer cut that holds 32 experts a layer
and an eighth of the vocabulary."""

import json
import os

from benchmark import arith_gdn as ag

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "qwen3-next-80b-a3b-train-d4e32.json"


def _model():
    with open(os.path.join(HERE, "..", "configs", NAME)) as f:
        return json.load(f)["model"]


def test_parameters_by_part_by_hand():
    m = _model()
    p = ag.params_by_part(m)
    # W_qkvz 2048 x (2048 + 2048 + 4096 + 4096), W_ba 2048 x 64, W_o 4096 x
    # 2048; beside the matmuls the conv 4 x 8192, A_log and dt_bias 32 each
    # and the gated norm's 128
    assert p["linear_matmul"] == 25_165_824 + 131_072 + 8_388_608
    assert p["linear_mixer"] == p["linear_matmul"] + 32_768 + 64 + 128 \
        == 33_718_464
    # W_q 2048 x (16 x 512), W_k and W_v 2048 x 512, W_o 4096 x 2048; the q
    # and k norms 256 each
    assert p["full_matmul"] == 16_777_216 + 2 * 1_048_576 + 8_388_608
    assert p["full_mixer"] == p["full_matmul"] + 512 == 27_263_488
    assert p["router"] == 2048 * 512 == 1_048_576
    assert p["shared_expert"] == p["one_expert"] == 3 * 2048 * 512 \
        == 3_145_728
    assert p["shared_gate"] == 2048 and p["norms"] == 4096
    assert ag.expert_layer_params(m) == 1_048_576 + 3_145_728 + 2048 \
        + 32 * 3_145_728 == 104_859_648
    assert p["embedding_and_head"] == 2 * 18_992 * 2048 == 77_791_232
    assert (ag.layers_of(m, ag.LINEAR), ag.layers_of(m, ag.FULL)) == (3, 1)


def test_param_count_by_hand():
    m = _model()
    linear = 33_718_464 + 104_859_648 + 4096
    full = 27_263_488 + 104_859_648 + 4096
    assert (linear, full) == (138_582_208, 132_127_232)
    assert ag.param_count(m) == 3 * linear + full + 77_791_232 + 2048 \
        == 625_667_136
    # the published model: 48 layers, 512 experts, the whole vocabulary
    whole = {**m, "num_hidden_layers": 48, "num_experts": 512,
             "vocab_size": 151_936}
    assert 79e9 < ag.param_count(whole) < 81e9          # the card's 80 B
    assert ag.layers_of(whole, ag.FULL) == 12


def test_the_rule_is_counted_at_a_chunk_of_64_by_hand():
    m = _model()
    # a value head a chunk: K K^T, Q K^T and the inverse on beta exp(G) K,
    # 2 x 64 x 64 x 128 each; the inverse on beta V and the masked product
    # with the new values, the same at d_v; three products with the state,
    # 2 x 64 x 128 x 128 each
    assert ag.rule_chunk_fwd_flops(m) == 5 * 1_048_576 + 3 * 2_097_152 \
        == 11_534_336
    # a token a layer: 32 heads, a 64th of a chunk
    assert ag.rule_fwd_flops(1, m, 8192) / 8192 == 5_767_168
    assert ag.rule_fwd_flops(2, m, 8192) == 2 * 32 * 128 * 11_534_336
    # a sequence that is no whole number of chunks pays whole chunks
    assert ag.rule_fwd_flops(1, m, 65) == 32 * 2 * 11_534_336
    # q, k once a KEY head (16 x 128 bfloat16 each), v in and o out (32 x
    # 128), g and beta (32 float32 each), a token
    assert ag.rule_min_bytes(1, m, 1) == 2 * (2 * 2048 + 2 * 4096) + 256 \
        == 24_832
    assert ag.rule_min_bytes(2, m, 8192) == 2 * 8192 * 24_832
    # the two roofs of a forward call lie together: 94.5 GFLOP are 0.480 ms
    # at the MXU's rate, 0.407 GB 0.497 ms at the HBM's, which is the roof
    compute = ag.rule_fwd_flops(2, m, 8192) / 197e12
    memory = ag.rule_min_bytes(2, m, 8192) / 819e9
    assert round(compute * 1e3, 3) == 0.480 and round(memory * 1e3, 3) == 0.497


def test_train_flops_per_token_by_hand():
    m = _model()
    assert ag.expected_rows_per_token(m) == 10 * 32 / 512 == 0.625
    assert ag.attention_fwd_flops(1, m, 8192) == 4 * 256 * 16 * (
        8192 * 8193 // 2)
    matmuls = (3 * 33_685_504 + 27_262_976
               + 4 * (1_048_576 + 3_145_728 + 2048) + 18_992 * 2048)
    routed = 4 * 0.625 * 3_145_728
    triangle = 3 * 4 * 256 * 16 * 8193 / 2
    rule = 3 * 3 * 5_767_168
    want = 6 * (matmuls + routed) + triangle + rule
    assert ag.train_flops_per_token(m, 8192) == want
    assert 1.40e9 < want < 1.45e9
    # the rows REALLY routed here take the place of the expectation
    assert ag.train_flops_per_token(m, 8192, 1.25) - want \
        == 6 * 4 * 0.625 * 3_145_728
