"""The latent-attention, routed-expert reference
(benchmark/reference/deepseek_v3_mla_moe.py) on the CPU: against the
program at the configuration's rehearsal sizes, and its own invariants
(causal, a relative rope, the share, and an expert given every token)."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers import train_model
from benchmark.reference import deepseek_v3_mla_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))


def _rehearsal():
    with open(os.path.join(HERE, "..", "configs",
                           "kanana-2-30b-a3b-train-d6e16.json")) as f:
        doc = json.load(f)
    model = {**doc["model"], **doc["rehearse"]["model"]}
    return doc, model


@pytest.fixture(scope="module")
def setup():
    from ray_tpu.models import latent_moe

    doc, model = _rehearsal()
    config = dataclasses.replace(
        train_model.build_config(doc["program"], model, doc["train"]),
        dtype=jnp.float32)
    params = latent_moe.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 129), 0, model["vocab_size"]))
    return latent_moe, config, params, tokens, ref.dims_from_config(model)


def test_the_program_agrees_at_the_rehearsal_sizes(setup):
    latent_moe, config, params, tokens, dims = setup
    got = latent_moe.token_nll(params, {"tokens": jnp.asarray(tokens)},
                               config)
    want = ref.batch_token_nll(params, tokens, dims)
    # the fused cross-entropy multiplies in bfloat16
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2)
    assert abs(float(want.mean()) - np.log(dims_vocab(params))) < 1.0


def dims_vocab(params):
    return params["lm_head"].shape[0]


def test_reference_is_causal_and_its_rope_is_relative(setup):
    _, _, params, tokens, dims = setup
    row = tokens[0, :-1]
    base = ref.Pass(params, row, dims).final
    changed = row.copy()
    changed[100] = (changed[100] + 1) % dims_vocab(params)
    after = ref.Pass(params, changed, dims).final
    np.testing.assert_array_equal(np.asarray(base[:100]),
                                  np.asarray(after[:100]))
    assert float(jnp.abs(base[100:] - after[100:]).max()) > 1e-4
    # rope: a pair's score depends on the distance between its positions
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 1, dims["rope"]))
    turned = ref._rope(jnp.broadcast_to(x[:1], x.shape), dims["theta"])
    scores = turned[:, 0] @ turned[:, 0].T
    np.testing.assert_allclose(np.asarray(jnp.diagonal(scores, 1)),
                               float(scores[0, 1]), rtol=1e-4)
    assert abs(float(scores[0, 1] - scores[0, 5])) > 1e-3


def test_the_share_leaves_out_what_the_other_experts_add(setup):
    _, _, params, tokens, dims = setup
    lp = jax.tree.map(lambda a: a[0], params["layers"]["seg01"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(2), (64, lp["router_w"].shape[0]))
    held = ref.whole_layer_ffn(h, lp, dims, (dims["first_held"],
                                             dims["held"]))
    sel, gates = ref._select(h, lp["router_w"], lp["router_bias"], dims)
    assert sel.shape == (64, dims["top_k"])
    assert int(sel.max()) < dims["router_width"] == 16
    # a token none of whose experts is held gets exactly nothing
    nothing = ~np.asarray(jnp.any(sel < dims["held"], axis=-1))
    assert nothing.any()
    assert not np.asarray(held)[nothing].any()
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), dims["scale"],
                               rtol=1e-5)


def test_an_expert_given_every_token_loses_none():
    """One expert of sixteen given EVERY token of 512, one a token: the
    reference weighs every expert on every token, so uneven routing is no
    case of its own, and the program's dropless layer agrees."""
    from ray_tpu.models import latent_moe

    config = latent_moe.LatentMoEConfig.tiny(
        dtype=jnp.float32, remat=False, num_experts_per_tok=1,
        num_hidden_layers=2)
    params = latent_moe.init_params(config, jax.random.PRNGKey(3))
    bias = params["layers"]["seg01"]["0"]["router_bias"]
    params["layers"]["seg01"]["0"]["router_bias"] = bias.at[:, 2].set(50.0)
    dims = ref.dims_from_config(
        {f.name: getattr(config, f.name) for f in dataclasses.fields(config)})
    row = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (513,), 0,
                                        256))
    run = ref.Pass(params, row[:-1], dims)
    operands, routed = run.routed_experts()
    sel, _ = ref._select(operands[0][0], operands[1], operands[2], dims)
    assert bool(jnp.all(sel == 2))
    assert float(jnp.abs(routed[0]).max(axis=-1).min()) > 0     # every token
    got = latent_moe.token_nll(params, {"tokens": jnp.asarray(row[None])},
                               config)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(run.token_nll(row[1:])), atol=2e-4)
