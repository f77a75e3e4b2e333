"""The lanes' mixing (ops/hyper_connection.py): the Pallas kernels, interpreted
on the CPU, and the XLA formulation, against the equations a step at a time
(`*_reference`, matrices as [.., n, n]): u, mix and X' forward, the
gradients of the stream, of the sublayer's result and of the three leaves,
the stream handed on from `hc_pre` to `hc_post`; the Sinkhorn rounds' row
and column sums; the clamp; the collapse; w_hc = 0 (the one-lane residual);
the shapes the rule sends to XLA; the path `dispatch.taken()` names."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch, hyper_connection as H

F32 = jnp.float32
HC = H.HC(4)


@pytest.fixture(params=["interpret", "xla"])
def path(request, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET",
                       "1" if request.param == "interpret" else "")
    return request.param


def _operands(b=1, s=256, d=128, n=4, seed=0, diagonal=2.0):
    k = jax.random.split(jax.random.key(seed), 6)
    width = 2 * n + n * n
    x = jax.random.normal(k[0], (b, s, n * d)).astype(jnp.bfloat16)
    w = jax.random.normal(k[1], (n * d, width)) / np.sqrt(n * d)
    scale = jnp.array([0.5, 0.7, 1.0])
    base = jnp.concatenate([
        jnp.full((n,), -np.log(n - 1.0)), jnp.zeros((n,)),
        (diagonal * jnp.eye(n)).reshape(-1)]) \
        + 0.3 * jax.random.normal(k[2], (width,))
    y = jax.random.normal(k[3], (b, s, d)).astype(jnp.bfloat16)
    return x, w, scale, base, y, k[4], k[5]


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def _round(pre, post, hc, hand_on=True):
    """hc_pre, a sublayer (y + u, rounded) and hc_post as one function of
    the operands, with two cotangents' worth of outputs."""
    def fn(x, w, scale, base, y, gx, gu):
        got = pre(x, w, scale, base, hc)
        u, mix = got[:2]
        x_on = got[2] if hand_on and len(got) > 2 else x
        out = post(x_on, (y.astype(F32) + u.astype(F32)).astype(x.dtype),
                   mix, hc)
        return (jnp.sum(out.astype(F32) * gx) + jnp.sum(u.astype(F32) * gu),
                (u, mix, out))
    return jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4), has_aux=True)


@pytest.fixture(scope="module")
def expected():
    x, w, scale, base, y, kx, ku = _operands()
    gx = jax.random.normal(kx, x.shape)
    gu = jax.random.normal(ku, y.shape)
    (_, out), grads = _round(H.hc_pre_reference, H.hc_post_reference, HC)(
        x, w, scale, base, y, gx, gu)
    return (x, w, scale, base, y, gx, gu), out, grads


def test_forward_is_the_reference(path, expected):
    args, want, _ = expected
    assert H.path(args[0], HC) == path
    (_, got), _ = _round(H.hc_pre, H.hc_post, HC)(*args)
    assert _rel(got[0], want[0]) < 4e-3        # u: one bfloat16 rounding
    np.testing.assert_allclose(got[1], want[1], atol=2e-6)      # mix
    assert _rel(got[2], want[2]) < 4e-3
    assert dispatch.taken()["hyper_connection"][path] >= 2


_SOUND_ROUNDS = H._mix_rows


def _low_rounds(z, hc):
    """`_mix_rows` with the Sinkhorn rounds' numbers rounded to bfloat16
    after every division: the lower precision the kernels rule out."""
    eps = hc.eps
    pre, post, *m = _SOUND_ROUNDS(z, hc._replace(iters=1))

    def low(v):
        return v.astype(jnp.bfloat16).astype(F32)

    m = [low(row) for row in m]
    for _ in range(hc.iters - 1):
        m = [low(row / (jnp.sum(row, -2, keepdims=True) + eps)) for row in m]
        sums = sum(m) + eps
        m = [low(row / sums) for row in m]
    return [pre, post, *m]


def test_float32_results_show_what_the_streams_rounding_hides(path,
                                                              monkeypatch):
    """`out_dtype` float32 (the model's probe): u and X' are the passes'
    own numbers, within 1e-5 of the reference's where the bfloat16 results
    are 2e-3 off, so that Sinkhorn rounds made in bfloat16 (1e-3 on comb)
    stand out of the first and drown in the second."""
    x, w, scale, base, *_ = _operands()

    def identity_round(dtype, pre=H.hc_pre, post=H.hc_post):
        u, mix, again = (*pre(x, w, scale, base, HC, out_dtype=dtype), x)[:3]
        return post(again, u, mix, HC, out_dtype=dtype)

    want = identity_round(F32, H.hc_pre_reference, H.hc_post_reference)
    exact, rounded = identity_round(F32), identity_round(None)
    assert exact.dtype == F32 and rounded.dtype == jnp.bfloat16
    assert _rel(exact, want) < 1e-5 and 1e-3 < _rel(rounded, want) < 4e-3
    if path == "interpret":     # the kernels trace `_mix_rows`
        H._pre_forward.clear_cache()
        with monkeypatch.context() as mp:
            mp.setattr(H, "_mix_rows", _low_rounds)
            low = _rel(identity_round(F32), want)
        H._pre_forward.clear_cache()
        assert 3e-4 < low < 5e-3
    g = jax.grad(lambda x: jnp.sum(H.hc_post(
        x, x[..., :128], H.hc_pre(x, w, scale, base, HC)[1], HC,
        out_dtype=F32)))(x)
    assert g.dtype == x.dtype and np.isfinite(np.asarray(g, np.float32)).all()


@pytest.mark.parametrize("hand_on", [True, False])
def test_gradients_are_the_reference(path, expected, hand_on):
    """Every operand's, with the stream handed on from `hc_pre` to `hc_post`
    (its cotangent an operand of the backward pass) and with x given to
    both."""
    args, _, want = expected
    _, got = _round(H.hc_pre, H.hc_post, HC, hand_on)(*args)
    for name, g, r in zip(("x", "w", "scale", "base", "y"), got, want):
        assert _rel(g, r) < (6e-3 if name in "xy" else 2e-4), name


def test_rows_and_columns_sum_to_one_after_twenty_rounds(path):
    """On logits about 0.5 apart; the rounds close in more slowly the more
    peaked the rows (diagonal 2 and logits 1 apart leave a worst row of 256
    tokens 5e-3 off after twenty, in the reference alike)."""
    x, w, scale, base, *_ = _operands(seed=3, diagonal=0.0)
    scale = scale.at[2].set(0.5)
    _, mix = H.hc_pre(x, w, scale, base, HC)[:2]
    pre, post, comb = H.mix_parts(mix, 4)
    assert comb.shape == (1, 256, 4, 4) and float(comb.min()) > 0
    assert float(jnp.abs(comb.sum(-1) - 1).max()) < 1e-4
    assert float(jnp.abs(comb.sum(-2) - 1).max()) < 1e-4
    one = H.hc_pre(x, w, scale, base, HC._replace(iters=1))[1]
    assert float(jnp.abs(H.mix_parts(one, 4)[2].sum(-1) - 1).max()) > 0.02
    np.testing.assert_array_equal(mix[..., HC.width:], 0.0)


def test_the_clamp_binds(path):
    """Logits of +-200 where the clamp holds them to +-30: the result is the
    reference's WITH the clamp, far from the one without, and finite."""
    x, w, scale, base, *_ = _operands(seed=5)
    scale = scale.at[2].set(200.0)
    got = H.mix_parts(H.hc_pre(x, w, scale, base, HC)[1], 4)[2]
    want = H.hc_mix_reference(x, w, scale, base, HC)[2]
    free = H.hc_mix_reference(x, w, scale, base, HC._replace(
        clamp_min=-1e9, clamp_max=1e9))[2]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(want - free).max()) > 1e-3
    g = jax.jit(jax.grad(lambda s: jnp.sum(
        H.hc_pre(x, w, s, base, HC)[1] ** 2)))(scale)
    r = jax.jit(jax.grad(lambda s: jnp.sum(
        H.hc_pre_reference(x, w, s, base, HC)[1] ** 2)))(scale)
    np.testing.assert_allclose(g, r, rtol=2e-3, atol=1e-6)


def test_zero_weights_are_the_one_lane_residual(path):
    """w_hc = 0 with the model's draws of base (pre sums to 1, post is 1,
    comb doubly stochastic): equal lanes stay equal and X' = x + y."""
    from ray_tpu.models import stack

    x, w, _, _, y, *_ = _operands(seed=7)
    lane = x[..., :128]
    lanes = jnp.concatenate([lane] * 4, -1)
    base = stack.hc_base(jax.random.key(1), (24,))
    scale = jnp.array(stack.HC_SCALE)
    u, mix, again = H.hc_pre(lanes, 0.0 * w, scale, base, HC)
    assert again is lanes or bool((again == lanes).all())
    assert _rel(u, lane) < 1e-5
    out = H.hc_post(again, y, mix, HC)
    want = (lane.astype(F32) + y.astype(F32)).astype(jnp.bfloat16)
    for i in range(4):
        assert _rel(out[..., i * 128:(i + 1) * 128], want) < 4e-3


def test_collapse_is_its_reference(path):
    x, w, scale, base, _, kx, _ = _operands(seed=9)
    w_head, scale_h, base_h = w[:, :4], scale[:1], base[:4]
    g = jax.random.normal(kx, (1, 256, 128))

    def loss(fn):
        return jax.jit(jax.value_and_grad(lambda x, w, s, b: jnp.sum(
            fn(x, w, s, b, HC).astype(F32) * g), argnums=(0, 1, 2, 3)))

    got, g_got = loss(H.hc_collapse)(x, w_head, scale_h, base_h)
    want, g_want = loss(H.hc_collapse_reference)(x, w_head, scale_h, base_h)
    assert abs(float(got - want)) < 2e-3 * abs(float(want)) + 0.5
    for a, r in zip(g_got, g_want):
        assert _rel(a, r) < 6e-3


@pytest.mark.parametrize("shape,dtype", [
    ((1, 256, 4 * 96), jnp.bfloat16),       # a lane no multiple of 128
    ((1, 200, 4 * 128), jnp.bfloat16),      # rows no multiple of the tile
    ((1, 256, 4 * 128), jnp.float32),       # a float32 stream
])
def test_off_the_kernels_shapes_goes_to_xla(monkeypatch, shape, dtype):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert H.path(jnp.zeros(shape, dtype), HC) == "xla"
    assert H.path(jnp.zeros((1, 256, 512), jnp.bfloat16), HC) == "interpret"
    assert H.path(jnp.zeros((1, 256, 5 * 128), jnp.bfloat16),
                  H.HC(5)) == "xla"        # 2 n + n^2 over 32


def test_operands_are_checked():
    x, w, scale, base, y, *_ = _operands()
    with pytest.raises(ValueError):
        H.hc_pre(x, w[:, :20], scale, base, HC)
    with pytest.raises(ValueError):
        H.hc_post(x, y[..., :64], jnp.zeros((1, 256, 128)), HC)
