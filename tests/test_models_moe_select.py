"""models/moe.py `select_experts`: the selection the three routers share
(scores in, the top k of scores + bias, gates by a stated rule), and the two
older routers as its thin callers: what they trace is what they traced
before the selection was shared."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import moe

T, H, E = 64, 32, 16


def _operands(seed=0):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kx, (T, H)),
            jax.random.normal(kw, (H, E)) / np.sqrt(H),
            0.05 * jax.random.normal(kb, (E,)))


def _probs(x, w):
    return jax.nn.softmax(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST),
                          axis=-1)


def test_a_raw_gate_at_k_1_carries_the_routers_gradient():
    """gate = p[sel] as it is: the gradient reaches the router's weight;
    renormalised at k = 1 every gate is `scale` and the gradient is zero
    (what `sigmoid_route` and `softmax_route` would give a top-1 model)."""
    x, w, bias = _operands()

    def gate_sum(w, rule):
        _, gates = moe.select_experts(_probs(x, w), bias,
                                      num_experts_per_token=1, gate_rule=rule,
                                      scale=2.5)
        return jnp.sum(gates)

    raw = jax.grad(gate_sum)(w, "raw")
    assert float(jnp.abs(raw).max()) > 1e-3
    idx, gates = moe.select_experts(_probs(x, w), bias,
                                    num_experts_per_token=1, gate_rule="raw")
    assert idx.shape == gates.shape == (T, 1) and idx.dtype == jnp.int32
    np.testing.assert_allclose(
        np.asarray(gates[:, 0]),
        np.asarray(_probs(x, w))[np.arange(T), np.asarray(idx[:, 0])])
    assert float(gates.max()) < 1.0     # a probability, no further scale
    flat = jax.grad(gate_sum)(w, "renormalised")
    assert float(jnp.abs(flat).max()) < 1e-6    # p / p: nothing but rounding
    _, ones = moe.select_experts(_probs(x, w), bias, num_experts_per_token=1,
                                 gate_rule="renormalised", scale=2.5)
    np.testing.assert_allclose(np.asarray(ones), 2.5, rtol=1e-6)


def test_the_bias_enters_the_selection_only_and_gets_no_gradient():
    x, w, bias = _operands(1)
    probs = _probs(x, w)
    plain, _ = moe.select_experts(probs, None, num_experts_per_token=1,
                                  gate_rule="raw")
    np.testing.assert_array_equal(np.asarray(plain[:, 0]),
                                  np.asarray(jnp.argmax(probs, axis=-1)))
    # a bias that lifts expert 3 above every probability sends all there,
    # and the gate stays the token's own probability for it
    lifted = jnp.zeros((E,)).at[3].set(2.0)
    idx, gates = moe.select_experts(probs, lifted, num_experts_per_token=1,
                                    gate_rule="raw")
    assert set(np.asarray(idx).ravel()) == {3}
    np.testing.assert_allclose(np.asarray(gates[:, 0]),
                               np.asarray(probs[:, 3]))
    g = jax.grad(lambda b: jnp.sum(moe.select_experts(
        probs, b, num_experts_per_token=2, gate_rule="raw")[1]))(bias)
    assert not np.asarray(g).any()


def test_an_unknown_rule_is_refused():
    x, w, _ = _operands()
    with pytest.raises(ValueError):
        moe.select_experts(_probs(x, w), None, num_experts_per_token=1,
                           gate_rule="softmax")


# The two older routers as they were before the selection was shared (PR
# 43's text): what they trace must not move, or three cells' step programs
# and their compile-cache keys move with it.

def _sigmoid_route_as_it_was(x, router_w, select_bias, *,
                             num_experts_per_token, scale):
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)),
        num_experts_per_token)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), gates


def _softmax_route_as_it_was(x, router_w, *, num_experts_per_token, scale):
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, idx = jax.lax.top_k(probs, num_experts_per_token)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), gates


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
@pytest.mark.parametrize("differentiated", [False, True])
def test_the_older_routers_trace_as_before(router, differentiated):
    x, w, bias = _operands(2)
    x = x.astype(jnp.bfloat16)
    now, was = {
        "sigmoid": (lambda x, w: moe.sigmoid_route(
            x, w, bias, num_experts_per_token=3, scale=2.5),
            lambda x, w: _sigmoid_route_as_it_was(
            x, w, bias, num_experts_per_token=3, scale=2.5)),
        "softmax": (lambda x, w: moe.softmax_route(
            x, w, num_experts_per_token=3, scale=2.5),
            lambda x, w: _softmax_route_as_it_was(
            x, w, num_experts_per_token=3, scale=2.5)),
    }[router]
    if differentiated:
        now, was = (jax.grad(lambda x, w, f=f: jnp.sum(f(x, w)[1] ** 2),
                             argnums=(0, 1)) for f in (now, was))
    assert str(jax.make_jaxpr(now)(x, w)) == str(jax.make_jaxpr(was)(x, w))
