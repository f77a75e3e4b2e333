"""Selective-scan kernels (interpreted on the CPU) against a sequential
scan written out here: forward and all six gradients, a sequence that is
no multiple of the chunk, channels that are no multiple of the channel
block, and state carried over chunk and channel-block edges."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import selective_scan as ss


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _inputs(b, t, c, n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, t, c)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (c, n)))
    B = jax.random.normal(ks[3], (b, t, n))
    C = jax.random.normal(ks[4], (b, t, n))
    D = jax.random.normal(ks[5], (c,))
    return x, dt, A, B, C, D


def _sequential(x, dt, A, B, C, D):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t; y_t = h_t C_t + D x_t: a
    Python loop over time, nothing shared with the op."""
    b, t, c = x.shape
    h = jnp.zeros((b, c, A.shape[1]), jnp.float32)
    ys = []
    for i in range(t):
        h = (jnp.exp(dt[:, i, :, None] * A) * h
             + (dt[:, i] * x[:, i])[:, :, None] * B[:, i, None, :])
        ys.append(jnp.sum(h * C[:, i, None, :], axis=-1) + D * x[:, i])
    return jnp.stack(ys, axis=1)


# (batch, time, channels, state, chunk)
_SHAPES = [
    (2, 32, 1024, 16, 16),      # two chunks, one channel block
    (1, 40, 2048, 16, 16),      # time no multiple of the chunk, 2 blocks
    (2, 24, 1100, 4, 8),        # channels no multiple of 1024: padded
    (1, 20, 96, 8, 64),         # one chunk longer than the sequence
]


@pytest.mark.parametrize("b,t,c,n,chunk", _SHAPES)
def test_forward_matches_the_sequential_scan(b, t, c, n, chunk):
    args = _inputs(b, t, c, n, seed=t + c)
    got = ss.selective_scan(*args, chunk=chunk)
    want = _sequential(*args)
    assert got.shape == want.shape and got.dtype == args[0].dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the XLA formulation (off TPU, no interpreter) is the same function
    np.testing.assert_allclose(
        np.asarray(ss.selective_scan_xla(*args, chunk=chunk)),
        np.asarray(want), atol=2e-5, rtol=2e-5)


@functools.lru_cache(maxsize=None)
def _all_six_gradients(b, t, c, n, chunk):
    """(the scan's, the sequential scan's) gradients by all six operands at
    one shape, each side ONE jitted program: the six cases of a shape differ
    only in which of the six they read."""
    args = _inputs(b, t, c, n, seed=7 + t)
    w = jax.random.normal(jax.random.key(3), args[0].shape)

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                argnums=tuple(range(6))))(*args)

    return (grads(lambda *a: ss.selective_scan(*a, chunk=chunk)),
            grads(_sequential))


@pytest.mark.parametrize("b,t,c,n,chunk", _SHAPES[:3])
@pytest.mark.parametrize("wrt", range(6), ids=["dx", "ddt", "dA", "dB",
                                               "dC", "dD"])
def test_each_gradient_matches_the_sequential_scan(wrt, b, t, c, n, chunk):
    got, want = (g[wrt] for g in _all_six_gradients(b, t, c, n, chunk))
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * max(scale, 1.0), rtol=1e-4)


def test_state_crosses_chunk_and_channel_block_edges():
    """An impulse at t = 0 in one channel of each channel block, decaying
    slowly: y at every later step, over three chunk edges, is the decayed
    impulse, and the other block's channels never see it."""
    b, t, c, n, chunk = 1, 32, 2048, 2, 8
    x = jnp.zeros((b, t, c)).at[0, 0, 5].set(1.0).at[0, 0, 1024 + 7].set(2.0)
    dt = jnp.full((b, t, c), 0.5)
    A = jnp.full((c, n), -0.1)
    B, C = jnp.ones((b, t, n)), jnp.ones((b, t, n))
    y = ss.selective_scan(x, dt, A, B, C, jnp.zeros((c,)), chunk=chunk)
    decay = np.exp(-0.05 * np.arange(t))            # exp(dt A) a step
    np.testing.assert_allclose(np.asarray(y[0, :, 5]), n * 0.5 * decay,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y[0, :, 1024 + 7]),
                               n * 1.0 * decay, rtol=1e-5)
    quiet = np.ones(c, bool)
    quiet[[5, 1024 + 7]] = False
    assert not np.asarray(y[0])[:, quiet].any()


def test_bfloat16_inputs_run_the_recurrence_in_float32():
    args = _inputs(1, 48, 1024, 16, seed=5)
    x16 = args[0].astype(jnp.bfloat16)
    got = ss.selective_scan(x16, *args[1:], chunk=16)
    assert got.dtype == jnp.bfloat16
    want = _sequential(x16.astype(jnp.float32), *args[1:])
    # only the OUTPUT is rounded to bfloat16 (8 bits of mantissa)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), rtol=2 ** -8, atol=1e-2)


def test_path_and_plan_are_recorded(monkeypatch):
    monkeypatch.setattr(ss.dispatch, "_taken", {})
    args = _inputs(1, 16, 128, 4)
    ss.selective_scan(*args, chunk=8)
    taken = ss.dispatch.taken()
    assert taken["selective_scan"] == {"interpret": 1}
    assert taken["selective_scan.plan"] == {
        "chunk8,channels1024,seq16,state4": 1}
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    ss.selective_scan(*args)
    assert ss.dispatch.taken()["selective_scan"] == {"interpret": 1, "xla": 1}
