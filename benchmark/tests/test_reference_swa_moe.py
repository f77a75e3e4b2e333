"""The windowed / full GQA, routed-expert reference
(benchmark/reference/laguna_swa_moe.py) on the CPU: against the program at
the configuration's rehearsal sizes, and its own invariants (causal, a
window that forgets, a relative rope over part of the head, the gate, the
share)."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers import train_model
from benchmark.reference import laguna_swa_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
FULL, SLIDING = "full_attention", "sliding_attention"


def _rehearsal():
    with open(os.path.join(HERE, "..", "configs",
                           "laguna-s-2.1-train-d5e8.json")) as f:
        doc = json.load(f)
    return doc, {**doc["model"], **doc["rehearse"]["model"]}


@pytest.fixture(scope="module")
def setup():
    from ray_tpu.models import swa_moe

    doc, model = _rehearsal()
    config = dataclasses.replace(
        train_model.build_config(doc["program"], model, doc["train"]),
        dtype=jnp.float32)
    params = swa_moe.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 129), 0, model["vocab_size"]))
    return swa_moe, config, params, tokens, ref.dims_from_config(model)


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text


def test_the_program_agrees_at_the_rehearsal_sizes(setup):
    swa_moe, config, params, tokens, dims = setup
    assert dims["kinds"] == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert dims["heads"] == (4, 6, 6, 6, 4) and dims["window"] == 32
    got = swa_moe.token_nll(params, {"tokens": jnp.asarray(tokens)}, config)
    want = ref.batch_token_nll(params, tokens, dims)
    # the fused cross-entropy multiplies in bfloat16
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2)
    assert abs(float(want.mean()) - np.log(params["lm_head"].shape[0])) < 1.0


def test_reference_is_causal_and_a_sliding_layer_forgets(setup):
    _, _, params, tokens, dims = setup
    row = tokens[0, :-1]
    base = ref.Pass(params, row, dims).final
    changed = row.copy()
    changed[100] = (changed[100] + 1) % params["lm_head"].shape[0]
    after = ref.Pass(params, changed, dims).final
    np.testing.assert_array_equal(np.asarray(base[:100]),
                                  np.asarray(after[:100]))
    assert float(jnp.abs(base[100:] - after[100:]).max()) > 1e-4
    # one sliding layer alone: position t sees keys t - 31 .. t and no other
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (96, n, 16))
               for i, n in ((2, 6), (3, 2), (4, 2)))
    out = ref._grouped_attention(q, k, v, 32)
    moved = ref._grouped_attention(q, k.at[10].add(1.0), v.at[10].add(1.0),
                                   32)
    differs = np.asarray(jnp.abs(out - moved).max(axis=(1, 2)) > 0)
    assert differs[10:42].all() and not differs[:10].any() \
        and not differs[42:].any()
    # and query head j reads KV head j // 3: heads 0-2 do not see KV head 1
    other = ref._grouped_attention(q, k.at[:, 1].multiply(2.0), v, 32)
    np.testing.assert_array_equal(np.asarray(out[:, :3]),
                                  np.asarray(other[:, :3]))
    assert float(jnp.abs(out[:, 3:] - other[:, 3:]).max()) > 1e-4


def test_rope_is_relative_and_a_full_layer_turns_half_the_head(setup):
    _, _, _, _, dims = setup
    ropes = {kind: dict(r) for kind, r in dims["rope"]}
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 1, 16))
    same = jnp.broadcast_to(x[:1], x.shape)
    for kind, turned_columns in ((SLIDING, 16), (FULL, 8)):
        turned = ref._rope(same, ropes[kind])
        # position 0 is not turned (times the attention factor, where one)
        factor = ropes[kind].get("attention_factor", 1.0)
        np.testing.assert_allclose(
            np.asarray(turned[0, 0, :turned_columns]),
            np.asarray(x[0, 0, :turned_columns]) * factor, rtol=1e-5)
        # the columns behind the rotary ones pass through
        np.testing.assert_array_equal(np.asarray(turned[..., turned_columns:]),
                                      np.asarray(same[..., turned_columns:]))
        # a pair's score depends on the distance between its positions
        scores = turned[:, 0] @ turned[:, 0].T
        np.testing.assert_allclose(np.asarray(jnp.diagonal(scores, 1)),
                                   float(scores[0, 1]), rtol=1e-4)
    assert abs(float(scores[0, 1] - scores[0, 5])) > 1e-6


def test_the_gate_scales_a_heads_output_before_the_output_projection(setup):
    _, _, params, _, dims = setup
    lp = jax.tree.map(lambda a: a[0], params["layers"]["seg01"]["0"])
    u = jax.random.normal(jax.random.PRNGKey(7), (64, lp["wq"].shape[0]))
    with jax.default_matmul_precision("highest"):
        base = ref._attention(u, lp, SLIDING, 6, dims)
        # a gate held open: sigmoid(large) = 1 for head 0 only changes it
        open0 = {**lp, "wg": lp["wg"].at[:, 0].set(0.0)}
        half = ref._attention(u, open0, SLIDING, 6, dims)
        wo0 = {**lp, "wo": lp["wo"].at[16:].set(0.0)}
        only0 = ref._attention(u, wo0, SLIDING, 6, dims)
        only0_half = ref._attention(
            u, {**wo0, "wg": open0["wg"]}, SLIDING, 6, dims)
    assert float(jnp.abs(base - half).max()) > 1e-5
    gate = jax.nn.sigmoid(u @ lp["wg"])[:, :1]
    np.testing.assert_allclose(np.asarray(only0 / gate),
                               np.asarray(only0_half / 0.5), rtol=2e-3,
                               atol=1e-5)


def test_the_share_leaves_out_what_the_other_experts_add(setup):
    _, _, params, _, dims = setup
    lp = jax.tree.map(lambda a: a[0], params["layers"]["seg02"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(2), (64, lp["router_w"].shape[0]))
    held = ref.whole_layer_ffn(h, lp, dims, (dims["first_held"],
                                             dims["held"]))
    sel, gates = ref._select(h, lp["router_w"], dims)
    assert sel.shape == (64, dims["top_k"])
    assert int(sel.max()) < dims["router_width"] == 16
    # a token none of whose experts is held gets exactly nothing
    nothing = ~np.asarray(jnp.any(sel < dims["held"], axis=-1))
    assert nothing.any()
    assert not np.asarray(held)[nothing].any()
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), dims["scale"],
                               rtol=1e-5)
    # the gates are the chosen probabilities, normalised, times 2.5
    probs = jax.nn.softmax(h @ lp["router_w"], axis=-1)
    picked = jnp.take_along_axis(probs, sel, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(picked / picked.sum(-1, keepdims=True) * 2.5), rtol=1e-5)
