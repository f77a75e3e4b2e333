"""ops/gated_delta.py: the chunked XLA form and the kernels (interpreted on
the CPU) against `gated_delta_reference`, the recurrence one step at a time:
forward and all five gradients, 2 key / 4 value heads, strong and weak
decay, beta at 0 and at 1, chunk edges inside the sequence, a sequence that
is no multiple of the chunk and one longer than a block of chunks (the state
and dL/dS carried from grid step to grid step); and the exact inverse."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch, gated_delta as gd

CHUNK = 16


def _inputs(t, weak, b=1, hk=2, hv=4, dk=16, dv=32, seed=0):
    ks = jax.random.split(jax.random.key(seed + t), 5)
    q, k = (jax.random.normal(key, (b, t, hk, dk)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    # weak: a step keeps 0.98 of the state, a chunk three quarters; strong:
    # a step keeps about half
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, hv))
                         - (4.0 if weak else 0.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    # beta exactly 0 (a step that writes nothing) and exactly 1
    beta = beta.at[:, 3::7].set(0.0).at[:, 5::11].set(1.0)
    return q, k, v, g, beta


def _rel(got, want):
    return float(jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want ** 2)))


def _rule(path, monkeypatch):
    if path == "xla":
        return lambda *a: gd.gated_delta_xla(*a, chunk=CHUNK)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    return lambda *a: gd.gated_delta_rule(*a, chunk=CHUNK)


# 80: five whole chunks; 72: padded to 80; 160: ten chunks, two blocks of 8
@pytest.mark.parametrize("path,t,weak", [
    ("xla", 80, False), ("xla", 80, True), ("xla", 72, True),
    ("kernels", 80, False), ("kernels", 80, True), ("kernels", 72, True),
    ("kernels", 160, True)])
def test_forward_and_gradients_match_the_recurrence(path, t, weak,
                                                    monkeypatch):
    rule = _rule(path, monkeypatch)
    args = _inputs(t, weak)
    want = gd.gated_delta_reference(*args)
    got = rule(*args)
    assert got.shape == want.shape and got.dtype == args[2].dtype
    assert _rel(got, want) < 3e-5
    w = jax.random.normal(jax.random.key(9), want.shape)
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for f in (rule, gd.gated_delta_reference)]
    for name, mine, ref in zip("q k v g beta".split(), *grads):
        assert mine.shape == ref.shape, name
        assert _rel(mine, ref) < 5e-5, name


def test_the_decay_and_beta_are_felt():
    """What the comparison above would not see if they were not: without
    the decay, or with beta = 1, the output is another."""
    q, k, v, g, beta = _inputs(80, True)
    want = gd.gated_delta_reference(q, k, v, g, beta)
    assert _rel(gd.gated_delta_xla(q, k, v, 0 * g, beta, CHUNK), want) > 0.05
    assert _rel(gd.gated_delta_xla(q, k, v, g, 0 * beta + 1, CHUNK),
                want) > 0.05


def test_bfloat16_operands_keep_a_float32_state(monkeypatch):
    """The operands as the model hands them over (bfloat16): the kernels
    against the recurrence on the SAME rounded operands differ by the
    output's one rounding; a state rounded to bfloat16 at every chunk's
    edge reads well above it."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v, g, beta = _inputs(160, True)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    want = gd.gated_delta_reference(q, k, v, g, beta)
    got = gd.gated_delta_rule(q, k, v, g, beta, chunk=CHUNK)
    assert got.dtype == jnp.bfloat16
    sound = _rel(got.astype(jnp.float32), want)
    assert sound < 4e-3
    plans = dispatch.taken()["gated_delta_rule.plan"]
    assert f"chunk{CHUNK},heads4over2,dk16,dv32,state_f32,bwd_pallas" in plans


def test_the_nilpotent_product_is_the_inverse():
    """(I + A)^-1 of a strictly lower triangular A by the product (I - A)
    (I + A^2)(I + A^4) .. against a triangular solve: exact to rounding,
    also where A's entries are as large as the rule's can be."""
    for n, scale in ((16, 1.0), (64, 0.1), (64, 0.3)):
        a = jnp.tril(scale * jax.random.normal(jax.random.key(n), (n, n)),
                     -1)
        want = jax.scipy.linalg.solve_triangular(
            jnp.eye(n) + a, jnp.eye(n), lower=True)
        got = gd._unit_lower_inverse(a)
        assert _rel(got, want) < 1e-4, (n, scale)
        assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0
