"""The benchmark's arithmetic against numbers worked by hand for
SmolLM2-1.7B (ISSUE 26): 1.711 B parameters, 196,608 B of KV a token,
6.04 and 11.5 GFLOP a trained token at 12 and 24 layers."""

import json
import os

import pytest

from benchmark import arith

HERE = os.path.dirname(os.path.abspath(__file__))


def _model(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_param_count_by_hand():
    m = _model("smollm2-1.7b-train-fsdp4")
    # embedding 49152 x 2048; a layer 4 x 2048^2 + 3 x 2048 x 8192 + 2 norms
    embed = 49152 * 2048
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192 + 2 * 2048
    assert embed == 100_663_296 and layer == 67_112_960
    assert arith.param_count(m) == embed + 24 * layer + 2048 == 1_711_376_384
    assert arith.param_count(_model("smollm2-1.7b-train-d12")) \
        == embed + 12 * layer + 2048 == 906_020_864


def test_kv_bytes_per_token():
    # 24 layers x (K and V) x 32 heads x 64 x 2 B
    assert arith.kv_bytes_per_token(_model("smollm2-1.7b-train-fsdp4")) == 196_608


def test_train_flops_per_token():
    # 6 x parameters (tied matrix once) + 12 x layers x hidden x sequence
    d12 = arith.train_flops_per_token(_model("smollm2-1.7b-train-d12"), 2048)
    full = arith.train_flops_per_token(_model("smollm2-1.7b-train-fsdp4"), 2048)
    assert d12 == 6 * 906_020_864 + 12 * 12 * 2048 * 2048
    assert round(d12 / 1e9, 2) == 6.04
    assert round(full / 1e9, 1) == 11.5
    # what bench.py's flops_per_token leaves out: the tied head's matmul
    assert 6 * 49152 * 2048 / full == pytest.approx(0.0526, abs=1e-3)


def test_paged_attention_bytes():
    m = _model("smollm2-1.7b-train-fsdp4")
    # one sequence of 100 tokens: K+V 100 x 2 x 2048 x 2 B, q and out
    # 2 x 2048 x 2 B, over 24 layers; a dead slot (0) costs nothing
    one = 24 * (100 * 2 * 2048 * 2 + 2 * 2048 * 2)
    assert arith.paged_attention_bytes([100, 0], m) == one
    assert arith.paged_attention_bytes([100, 100], m) == 2 * one
    assert arith.decode_bytes_per_iteration([100], m) \
        == 2 * 1_711_376_384 + one


def test_flash_flops():
    # 4 x head_dim operations for each attended (query, key) pair
    assert arith.flash_fwd_flops(1, 1, 4, 64, causal=False) == 4 * 16 * 64
    assert arith.flash_fwd_flops(1, 1, 4, 64, causal=True) == 4 * 10 * 64
    assert arith.flash_fwd_flops(5, 32, 2048, 64) \
        == 4 * 5 * 32 * (2048 * 2049 / 2) * 64


def test_peaks_known_and_unknown():
    p = arith.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]
    with pytest.raises(KeyError):
        arith.peaks("TPU v9 imaginary")
