"""The gated-delta-rule / gated-full-attention reference
(benchmark/reference/qwen3_next_gdn_moe.py) on the CPU: its recurrence
against a second, chunk-free formulation, its gradient against finite
differences at a tiny size, against the program at the configuration's
rehearsal sizes, and its own invariants (causal, a decay that forgets, a
relative rope over a quarter of the head, the gates, the share)."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers import train_model
from benchmark.reference import qwen3_next_gdn_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "qwen3-next-80b-a3b-train-d4e32.json"


def _rehearsal():
    with open(os.path.join(HERE, "..", "configs", NAME)) as f:
        doc = json.load(f)
    return doc, {**doc["model"], **doc["rehearse"]["model"]}


@pytest.fixture(scope="module")
def setup():
    from ray_tpu.models import gdn_moe

    doc, model = _rehearsal()
    config = dataclasses.replace(
        train_model.build_config(doc["program"], model, doc["train"]),
        dtype=jnp.float32, fused_ce=False)
    params = gdn_moe.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 97), 0, model["vocab_size"]))
    return gdn_moe, config, params, tokens, ref.dims_from_config(model)


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text


def _rule_inputs(T=70, heads=3, dk=8, dv=5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (T, heads, dk))
    k = jax.random.normal(ks[1], (T, heads, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, heads, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (T, heads)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, heads)))
    return q, k, v, g, beta


def test_the_recurrence_is_the_closed_form_written_without_a_state():
    """A step is S <- (I - beta k k^T) exp(g) S + beta k v^T, so unrolled
    S_t = sum_j (prod_{j < i <= t} exp(g_i) (I - beta_i k_i k_i^T)) beta_j
    k_j v_j^T: a second formulation with no running state and no chunk, a
    Python loop over pairs of steps in float64 on the host."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in _rule_inputs(T=12))
    T, heads, dk = k.shape
    want = np.zeros((T, heads, v.shape[-1]))
    for h in range(heads):
        for t in range(T):
            S = np.zeros((dk, v.shape[-1]))
            for j in range(t + 1):
                term = beta[j, h] * np.outer(k[j, h], v[j, h])
                for i in range(j + 1, t + 1):
                    term = np.exp(g[i, h]) * (
                        np.eye(dk) - beta[i, h] * np.outer(k[i, h], k[i, h])
                    ) @ term
                S = S + term
            want[t, h] = S.T @ q[t, h]
    got = ref._recurrence(*(jnp.asarray(a, jnp.float32)
                            for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-4)


def test_the_recurrences_gradient_matches_finite_differences():
    args = _rule_inputs(T=70)        # over a group's edge (64 steps)
    w = jax.random.normal(jax.random.PRNGKey(9), (70, 3, 5))

    def loss(*a):
        return jnp.sum(ref._recurrence(*a) * w)

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
        for n, (a, grad) in enumerate(zip(args, grads)):
            d = jax.random.normal(jax.random.PRNGKey(20 + n), a.shape)
            eps = 1e-2
            up, down = (loss(*args[:n], a + s * eps * d, *args[n + 1:])
                        for s in (1, -1))
            numeric = float(up - down) / (2 * eps)
            assert abs(numeric - float(jnp.sum(grad * d))) \
                <= 2e-2 * abs(numeric) + 1e-3, n


def test_a_decayed_state_forgets_and_beta_zero_writes_nothing():
    q, k, v, g, beta = _rule_inputs()
    out = ref._recurrence(q, k, v, g, beta)
    # causal: the first 30 outputs do not see step 30 on
    again = ref._recurrence(q, k, v.at[30:].set(0.0), g, beta)
    np.testing.assert_array_equal(np.asarray(out[:30]), np.asarray(again[:30]))
    # a strong decay at step 40 cuts what came before it off
    cut = g.at[40].set(-50.0)
    a = ref._recurrence(q, k, v, cut, beta)
    b = ref._recurrence(q, k, v.at[:40].set(7.0), cut, beta)
    np.testing.assert_allclose(np.asarray(a[40:]), np.asarray(b[40:]),
                               atol=1e-5)
    # beta = 0 everywhere: nothing is ever written
    assert float(jnp.abs(ref._recurrence(q, k, v, g, 0 * beta)).max()) == 0.0


def test_the_reference_agrees_with_the_program_at_the_rehearsal_size(setup):
    gdn_moe, config, params, tokens, dims = setup
    got = gdn_moe.token_nll(params, {"tokens": jnp.asarray(tokens)}, config)
    want = ref.batch_token_nll(params, tokens, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def test_the_probe_gives_the_rule_alone(setup):
    gdn_moe, config, params, tokens, dims = setup
    run = ref.Pass(params, tokens[0, :-1], dims)
    (q, k, v, g, beta), o = run.gated_delta_rule()
    assert q.shape == (1, 96, dims["hk"], dims["dk"])
    assert v.shape == o.shape == (1, 96, dims["hv"], dims["dv"])
    # normalised: |k| = 1, |q| = 1 / sqrt(d_k); a decay's log and a gate
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(k, axis=-1)), 1.0,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(q, axis=-1)),
                               dims["dk"] ** -0.5, atol=1e-3)
    assert float(g.max()) < 0 and 0 < float(beta.min()) \
        and float(beta.max()) < 1
    got = gdn_moe.gated_delta_rule(q, k, v, g, beta, config=config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(o), atol=2e-5)


def test_rope_turns_the_first_quarter_and_is_relative():
    x = jax.random.normal(jax.random.PRNGKey(3), (20, 2, 64))
    y = ref._rope(x, 16, 1e7)
    np.testing.assert_array_equal(np.asarray(y[..., 16:]),
                                  np.asarray(x[..., 16:]))
    np.testing.assert_array_equal(np.asarray(y[0]), np.asarray(x[0]))
    assert float(jnp.abs(y[5, :, :16] - x[5, :, :16]).max()) > 0.1
    # q . k depends on the distance alone: shift both by three positions
    q = jnp.broadcast_to(x[:1], x.shape)
    k = jnp.broadcast_to(x[1:2], x.shape)
    dots = jnp.einsum("thd,shd->ts", ref._rope(q, 16, 1e7),
                      ref._rope(k, 16, 1e7))
    np.testing.assert_allclose(np.asarray(dots[3:, 3:]),
                               np.asarray(dots[:-3, :-3]), atol=1e-3)


def test_the_gates_and_the_zero_centred_norm_are_felt(setup):
    """A zero weight is the identity scale; the shared expert's gate closes
    it; the full layer's gate is an element's."""
    _, _, params, _, dims = setup
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 64))
    np.testing.assert_allclose(
        np.asarray(ref._zero_centred_norm(x, jnp.zeros(64), 1e-6)),
        np.asarray(x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)),
        rtol=1e-6)
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      params["layers"]["seg01"]["0"])
    shut = {**lp, "shared_expert_gate": 0 * lp["shared_expert_gate"]}
    np.testing.assert_allclose(
        np.asarray(ref._gated_shared(x, shut)),
        0.5 * np.asarray(ref._swiglu(x, lp["shared_gate"], lp["shared_up"],
                                     lp["shared_down"])), rtol=1e-5)
    u = jax.random.normal(jax.random.PRNGKey(5), (12, 64))
    d = dict(dims)
    base = ref._full_attention(u, lp, d)
    # W_q's gate columns of head 0 at zero: head 0's output halves, the
    # other heads' stay
    wq = lp["wq"].reshape(64, d["heads"], 2, d["d"])
    half = {**lp, "wq": wq.at[:, 0, 1].set(0.0).reshape(lp["wq"].shape)}
    wo_head0 = {**lp, "wo": lp["wo"].at[d["d"]:].set(0.0)}
    wo_half = {**half, "wo": wo_head0["wo"]}
    assert not np.allclose(np.asarray(ref._full_attention(u, half, d)),
                           np.asarray(base))
    a, b = (ref._full_attention(u, p, d) for p in (wo_head0, wo_half))
    assert float(jnp.abs(a).max()) > 0 and not np.allclose(np.asarray(a),
                                                           np.asarray(b))


def test_the_share_is_the_held_experts_terms(setup):
    _, _, params, _, dims = setup
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      params["layers"]["seg00"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(6), (40, 64))
    sel, gates = ref._select(h, lp["router_w"], dims)
    assert sel.shape == (40, dims["top_k"])
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    routed = ref.whole_layer_ffn(h, lp, dims, (0, dims["held"]),
                                 with_shared=False)
    # a token none of whose experts is held gets nothing from the share
    none_held = ~np.asarray((sel < dims["held"]).any(-1))
    assert none_held.any()
    assert float(jnp.abs(routed[none_held]).max()) == 0.0
    both = ref.whole_layer_ffn(h, lp, dims, (0, dims["held"]))
    np.testing.assert_allclose(np.asarray(both - routed),
                               np.asarray(ref._gated_shared(h, lp)),
                               atol=1e-5)
