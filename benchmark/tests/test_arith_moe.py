"""The latent-attention, routed-expert model's arithmetic against numbers
worked by hand for Kanana-2-30B-A3B (ISSUE 34): hidden 2048, 32 heads of
192 / 128, latent 512 + rope 64, dense 6144, experts 768 wide, 6 of 128 a
token, 2 shared; 687,502,976 parameters in the six-layer cut that holds 16
experts a layer and an eighth of the vocabulary."""

import json
import os

import pytest

from benchmark import arith_moe as am

HERE = os.path.dirname(os.path.abspath(__file__))


def _model():
    with open(os.path.join(HERE, "..", "configs",
                           "kanana-2-30b-a3b-train-d6e16.json")) as f:
        return json.load(f)["model"]


def test_parameters_by_kind_by_hand():
    got = am.params_by_kind(_model())
    # W_q 2048 x 32 x 192, W_kva 2048 x 576, the latent norm 512,
    # W_kvb 512 x 32 x 256, W_o 4096 x 2048
    assert got["attention"] == (12_582_912 + 1_179_648 + 512 + 4_194_304
                                + 8_388_608) == 26_345_984
    assert got["dense_ffn"] == 3 * 2048 * 6144 == 37_748_736
    assert got["router"] == 2048 * 128 + 128 == 262_272
    assert got["shared_expert"] == 3 * 2048 * 1536 == 9_437_184
    assert got["one_expert"] == 3 * 2048 * 768 == 4_718_592
    assert got["embedding_and_head"] == 2 * 16_032 * 2048 == 65_667_072


def test_param_count_by_hand():
    m = _model()
    outside = 26_345_984 + 4096         # attention + the layer's two norms
    dense = outside + 37_748_736
    expert = outside + 262_272 + 9_437_184 + 16 * 4_718_592
    assert dense == 64_098_816 and expert == 111_547_008
    assert am.param_count(m) == 65_667_072 + 2048 + dense + 5 * expert \
        == 687_502_976
    # the published model: 47 expert layers of 128 experts, the vocabulary
    full = {**m, "num_hidden_layers": 48, "n_routed_experts": 128,
            "vocab_size": 128_256}
    assert 29e9 < am.param_count(full) < 32e9          # the card's 30 B


def test_train_flops_per_token_by_hand():
    m = _model()
    assert am.expected_rows_per_token(m) == 6 * 16 / 128 == 0.75
    # forward MFLOP a token a layer: the projections 2 x 26,345,472
    proj = 2 * (26_345_984 - 512)
    assert proj == 52_690_944
    # attention at 8192: 2 x 320 x 32 heads x 4096.5 pairs a query
    attn = 2 * 320 * 32 * 8193 / 2
    assert attn == 83_896_320
    shared, routed = 2 * 9_437_184, 2 * 0.75 * 4_718_592
    assert shared == 18_874_368 and routed == 7_077_888
    dense_ffn, router, head = 2 * 37_748_736, 2 * 2048 * 128, 2 * 16_032 * 2048
    forward = (6 * (proj + attn) + dense_ffn
               + 5 * (router + shared + routed) + head)
    assert am.train_flops_per_token(m, 8192) == pytest.approx(3 * forward)
    assert 3 * forward == pytest.approx(3.2792e9, rel=1e-4)
    # the deployment's eight data-parallel chips would send 6 rows a token
    more = am.train_flops_per_token(m, 8192, rows_per_token=6.0)
    assert more - 3 * forward == pytest.approx(
        6 * 5 * (6.0 - 0.75) * 4_718_592)


def test_attention_forward_flops_by_hand():
    got = am.attention_fwd_flops(2, _model(), 8192)
    assert got == 2 * 320 * 32 * 2 * (8192 * 8193 / 2) \
        == pytest.approx(1.3746e12, rel=1e-4)


def test_grouped_matmul_operations_and_least_bytes_by_hand():
    sizes = [768.0] * 16
    assert am.grouped_matmul_flops(sizes, 2048, 768) \
        == 2 * 12_288 * 2048 * 768 == pytest.approx(3.8655e10, rel=1e-4)
    # rows in and out in bfloat16, sixteen matrices of 2048 x 768 once
    assert am.grouped_matmul_min_bytes(sizes, 2048, 768) \
        == 2 * (12_288 * (2048 + 768) + 16 * 2048 * 768) == 119_537_664
    # an empty group's matrix is not needed; the rows' sum is what counts
    uneven = [12_288.0] + [0.0] * 15
    assert am.grouped_matmul_flops(uneven, 2048, 768) \
        == am.grouped_matmul_flops(sizes, 2048, 768)
    assert am.grouped_matmul_min_bytes(uneven, 2048, 768) \
        == 2 * (12_288 * 2816 + 2048 * 768)
    assert am.grouped_matmul_min_bytes([0.0] * 16, 2048, 768) == 0
    # compute over the bf16 peak against bytes over the HBM peak: at 768
    # rows a group the kernel is compute-bound (196 us against 146 us)
    assert 3.8655e10 / 197e12 > 119_537_664 / 819e9
