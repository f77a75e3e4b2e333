"""What the tests/test_ops_attention*.py files share: the kernels run
interpreted on the CPU (an autouse fixture, which each file imports), random
operands, a value with its three gradients, the explicit-mask reference, two
readers of what a call left behind, and rope tables whose products are
exact."""

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _rand_qkv(seed, b, s, h, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32)
                 for k in ks)


def _grads_and_value(fn, q, k, v, w):
    """fn(q, k, v) and the gradients of sum(fn * w) by q, k and v, from ONE
    jitted program: one forward, and no primitive dispatched alone."""
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * w), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, grads


def _masked_reference(sq, sk, d, causal, window):
    """Attention under the explicit mask, end-aligned (written out here)."""
    behind = (jnp.arange(sq)[:, None] + (sk - sq)) - jnp.arange(sk)[None, :]
    seen = jnp.ones((sq, sk), bool)
    if causal:
        seen = behind >= 0
    if window is not None:
        seen = seen & (behind < window)

    def masked(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    return masked


def _pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for val in eqn.params.values():
            inner = getattr(val, "jaxpr", val)      # a ClosedJaxpr's own
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner)
    return found


def _new_plans(before):
    from ray_tpu.ops import dispatch

    after = dispatch.taken()
    return {op: {p: n - before.get(op, {}).get(p, 0)
                 for p, n in after.get(op, {}).items()
                 if n - before.get(op, {}).get(p, 0)}
            for op in ("flash_attention", "flash_attention.plan")}


def _rope_tables(b, sk, d, starts=(3, 500)):
    """(cos, sin) [b, sk, d/2] float32 at positions that differ by row and
    do not start at 0, each value cut to the eight bits a bfloat16 holds:
    a bfloat16 operand times such a value is exact in float32, so x * cos
    + y * sin is rounded once whether or not the CPU's compiler fuses the
    multiply into the add (it does in one program and not in the other,
    which moves one rounding in 2 ** 16 of bfloat16 values; the TPU's vector
    unit has no such fused form to choose).  The bit-for-bit tests below
    test the kernels, not the host's code generator."""
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = (jnp.asarray(starts[:b], jnp.float32)[:, None]
           + jnp.arange(sk, dtype=jnp.float32)[None, :])
    freqs = pos[:, :, None] * inv
    return tuple(t.astype(jnp.bfloat16).astype(jnp.float32)
                 for t in (jnp.cos(freqs), jnp.sin(freqs)))
