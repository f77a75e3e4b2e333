"""The linear mixer's chain (ops/mixer_chain.py): the Pallas kernels,
interpreted on the CPU, and the XLA formulation, against a plain float32 loop
over the taps and the heads written out here: q, k, v and the gradients of
qkv and conv_w; position 0 of every row of the batch, a tile's boundary,
several row tiles and head blocks; the shapes the rule sends to XLA; the path
`dispatch.taken()` names."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch, mixer_chain as mc

TAPS = 4
F32 = jnp.float32


@pytest.fixture(params=["interpret", "xla"])
def path(request, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET",
                       "1" if request.param == "interpret" else "")
    return request.param


def _loop(qkv, conv_w, key_heads, d_k, q_scale):
    """The chain in float32, a tap and a head at a time."""
    x = qkv.astype(F32)
    b, t, channels = x.shape
    conv = jnp.zeros_like(x)
    for j in range(conv_w.shape[0]):
        back = conv_w.shape[0] - 1 - j      # tap j reads `back` rows before
        conv = conv.at[:, back:].add(x[:, :t - back] * conv_w[j].astype(F32))
    a = conv / (1.0 + jnp.exp(-conv))
    keys = key_heads * d_k
    heads = []
    for h in range(2 * key_heads):
        head = a[..., h * d_k:(h + 1) * d_k]
        length = jnp.sqrt(jnp.sum(head * head, -1, keepdims=True) + mc.L2_EPS)
        heads.append((q_scale if h < key_heads else 1.0) * head / length)
    qk = jnp.concatenate(heads, -1)
    return qk[..., :keys], qk[..., keys:], a[..., 2 * keys:]


def _operands(b, t, key_heads, d_k, value_columns, dtype, seed=0):
    rng = np.random.default_rng(seed + t + key_heads)
    channels = 2 * key_heads * d_k + value_columns
    qkv = jnp.asarray(rng.standard_normal((b, t, channels)), dtype)
    conv_w = jnp.asarray(0.5 * rng.standard_normal((TAPS, channels)), F32)
    cotangents = [jnp.asarray(rng.standard_normal((b, t, n)), F32)
                  for n in (key_heads * d_k, key_heads * d_k, value_columns)]
    return qkv, conv_w, cotangents


def _weighted(fn, cotangents):
    def loss(qkv, conv_w):
        return sum(jnp.sum(o.astype(F32) * c)
                   for o, c in zip(fn(qkv, conv_w), cotangents))
    return loss


# name -> rows of the batch, positions, key heads, their width, v's columns;
# the last column the path the rule takes when the interpreter is asked for
SHAPES = {
    "three_row_tiles_three_head_blocks": (2, 48, 3, 128, 512, "interpret"),
    "one_tile_blocks_of_two_heads": (1, 32, 2, 128, 512, "interpret"),
    "a_head_of_two_lane_tiles": (1, 16, 1, 256, 256, "interpret"),
    "heads_of_32_go_to_xla": (2, 32, 4, 32, 256, "xla"),
    "rows_no_multiple_of_the_tile_go_to_xla": (1, 40, 2, 128, 512, "xla"),
    "value_columns_no_lane_tiles_go_to_xla": (1, 32, 1, 128, 192, "xla"),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_chain_and_its_gradients_match_a_float32_loop(name, dtype, path,
                                                      monkeypatch):
    b, t, key_heads, d_k, values, interpreted = SHAPES[name]
    qkv, conv_w, cotangents = _operands(b, t, key_heads, d_k, values, dtype)
    q_scale = 1.0 / math.sqrt(d_k)
    monkeypatch.setattr(dispatch, "_taken", {})
    want_path = interpreted if path == "interpret" else "xla"
    assert mc.path(qkv, conv_w, key_heads, d_k) == want_path

    @jax.jit
    def op(qkv, conv_w):
        return mc.conv_silu_l2norm(qkv, conv_w, key_heads, d_k, q_scale)

    @jax.jit
    def loop(qkv, conv_w):
        return _loop(qkv, conv_w, key_heads, d_k, q_scale)

    got = op(qkv, conv_w)
    assert dispatch.taken()["mixer_chain"] == {want_path: 1}
    want = loop(qkv, conv_w)
    # the kernel rounds once, on the way out; the XLA formulation multiplies
    # the taps and takes SiLU in qkv's dtype
    kernel = want_path == "interpret"
    tol = (1e-5 if dtype == jnp.float32
           else 2 ** -8 if kernel else 2 ** -5)
    for g, w, n in zip(got, want, (key_heads * d_k,) * 2 + (values,)):
        assert g.shape == (b, t, n) and g.dtype == dtype
        np.testing.assert_allclose(np.asarray(g.astype(F32)), np.asarray(w),
                                   rtol=tol, atol=tol)
    got_g, want_g = (
        jax.jit(jax.grad(_weighted(fn, cotangents), argnums=(0, 1)))(
            qkv, conv_w) for fn in (op, loop))
    assert got_g[0].dtype == dtype and got_g[1].dtype == F32
    for g, w in zip(got_g, want_g):
        scale = float(jnp.abs(w.astype(F32)).max())
        np.testing.assert_allclose(
            np.asarray(g.astype(F32)), np.asarray(w.astype(F32)),
            rtol=tol, atol=(tol if dtype == jnp.float32 or kernel
                            else 2 ** -3) * scale)


def test_position_0_sees_no_earlier_row_and_no_other_row_of_the_batch(path):
    """Row 1 of the batch with everything before it changed: the same; the
    first three positions are the taps' last columns alone."""
    key_heads, d_k, values = 2, 128, 256
    qkv, conv_w, _ = _operands(3, 32, key_heads, d_k, values, F32)
    other = qkv.at[0].set(7.0).at[2].set(-3.0)
    got, moved = (mc.conv_silu_l2norm(x, conv_w, key_heads, d_k, 1.0)
                  for x in (qkv, other))
    for g, m in zip(got, moved):
        np.testing.assert_array_equal(np.asarray(g[1]), np.asarray(m[1]))
    alone = qkv[:, 0] * conv_w[TAPS - 1]
    np.testing.assert_allclose(
        np.asarray(got[2][:, 0]),
        np.asarray((alone / (1.0 + jnp.exp(-alone)))[:, 2 * key_heads * d_k:]),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("position", [13, 14, 15, 16, 17, 18])
def test_a_tile_boundary_passes_values_forward_and_cotangents_back(
        position, monkeypatch):
    """One position changed three either side of the boundary between the
    row tiles 0-15 and 16-31: v moves at that position and the three behind
    it and nowhere else, and the cotangent of one v row reaches that row of
    qkv and the three before it, as the loop's does."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    key_heads, d_k, values = 1, 128, 128
    qkv, conv_w, _ = _operands(1, 48, key_heads, d_k, values, F32)
    assert mc._plan(qkv, key_heads, d_k)[0] == 16       # three row tiles

    @jax.jit
    def v_of(x):
        return mc.conv_silu_l2norm(x, conv_w, key_heads, d_k, 1.0)[2]

    moved = np.asarray(jnp.abs(v_of(qkv.at[0, position].add(1.0))
                               - v_of(qkv)).max(-1)[0]) > 0
    assert list(np.flatnonzero(moved)) == list(range(position,
                                                     position + TAPS))
    grad = jax.jit(jax.grad(lambda x: jnp.sum(v_of(x)[0, position])))(qkv)
    want = jax.jit(jax.grad(lambda x: jnp.sum(
        _loop(x, conv_w, key_heads, d_k, 1.0)[2][0, position])))(qkv)
    reached = np.asarray(jnp.abs(grad).max(-1)[0]) > 0
    assert list(np.flatnonzero(reached)) == list(
        range(position - TAPS + 1, position + 1))
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_the_kernel_is_for_one_device_and_at_most_nine_taps(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    qkv, conv_w, _ = _operands(1, 32, 1, 128, 128, F32)
    assert mc.path(qkv, conv_w, 1, 128) == "interpret"
    assert mc.path(qkv, jnp.zeros((10, 384)), 1, 128) == "xla"
    with pytest.raises(ValueError, match="conv_w"):
        mc.conv_silu_l2norm(qkv, conv_w[:, :256], 1, 128, 1.0)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "")
    assert mc.path(qkv, conv_w, 1, 128) == "xla"        # a CPU run
