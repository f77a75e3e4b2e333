"""The mixers' chains (ops/mixer_chain.py): the Pallas kernels, interpreted
on the CPU, and the XLA formulations, against plain float32 loops over the
taps, the heads and the groups written out here.  The linear mixer's: q, k, v
and the gradients of qkv and conv_w.  The Mamba-2 mixer's two passes at the
tiny configuration's widths (256 + 256 + 256 columns, two groups of 128): x,
B, C with the gradients of xBC, the taps and the BIAS, and the gated norm by
groups with those of y, z and its weight.  Position 0 of every row of the
batch, a tile's boundary, several row tiles, head, column and group blocks;
the shapes the rule sends to XLA; the path `dispatch.taken()` names."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch, mixer_chain as mc

TAPS = 4
EPS = 1e-5
F32 = jnp.float32


@pytest.fixture(params=["interpret", "xla"])
def path(request, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET",
                       "1" if request.param == "interpret" else "")
    return request.param


def _conv_silu(x, conv_w, conv_b=None):
    """silu(bias + the causal convolution) in float32, a tap at a time."""
    x = x.astype(F32)
    t = x.shape[1]
    conv = jnp.zeros_like(x) if conv_b is None else x * 0.0 + conv_b
    for j in range(conv_w.shape[0]):
        back = conv_w.shape[0] - 1 - j      # tap j reads `back` rows before
        conv = conv.at[:, back:].add(x[:, :t - back] * conv_w[j].astype(F32))
    return conv / (1.0 + jnp.exp(-conv))


def _loop(qkv, conv_w, key_heads, d_k, q_scale):
    """The chain in float32, a tap and a head at a time."""
    a = _conv_silu(qkv, conv_w)
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


def _split_loop(x, conv_w, conv_b, widths):
    a = _conv_silu(x, conv_w, conv_b)
    cuts = np.cumsum((0,) + tuple(widths))
    return tuple(a[..., lo:hi] for lo, hi in zip(cuts, cuts[1:]))


def _norm_loop(y, z, w, groups, eps=EPS):
    """rmsnorm_g(y silu(z)) w in float32, a group at a time."""
    y, z = y.astype(F32), z.astype(F32)
    gated = y * z / (1.0 + jnp.exp(-z))
    width = y.shape[-1] // groups
    out = []
    for g in range(groups):
        group = gated[..., g * width:(g + 1) * width]
        out.append(group / jnp.sqrt(
            jnp.mean(group * group, -1, keepdims=True) + eps)
            * w[g * width:(g + 1) * width])
    return jnp.concatenate(out, -1)


def _with_gradients(fn, cotangents):
    """fn's results (a tuple) and the gradients of sum(result x cotangent)
    by every operand, in ONE program."""
    @jax.jit
    def run(*operands):
        out, vjp = jax.vjp(fn, *operands)
        return out, vjp(tuple(c.astype(o.dtype)
                              for o, c in zip(out, cotangents)))
    return run


# name -> the op's kind and its shapes; the last column the path the rule
# takes when the interpreter is asked for.  "l2norm": rows of the batch,
# positions, key heads, their width, v's columns.  "split": rows, positions,
# the widths of x, B, C.  "norm": rows, positions, columns, groups.
SHAPES = {
    "three_row_tiles_three_head_blocks":
        ("l2norm", 2, 48, 3, 128, 512, "interpret"),
    "one_tile_blocks_of_two_heads": ("l2norm", 1, 32, 2, 128, 512, "interpret"),
    "a_head_of_two_lane_tiles": ("l2norm", 1, 16, 1, 256, 256, "interpret"),
    "heads_of_32_go_to_xla": ("l2norm", 2, 32, 4, 32, 256, "xla"),
    "rows_no_multiple_of_the_tile_go_to_xla":
        ("l2norm", 1, 40, 2, 128, 512, "xla"),
    "value_columns_no_lane_tiles_go_to_xla":
        ("l2norm", 1, 32, 1, 128, 192, "xla"),
    # the tiny configuration's widths: a block of 256 columns ends where x
    # meets B and B meets C (the backward walks blocks of 128 inside them)
    "split_three_row_tiles_blocks_end_at_the_cuts":
        ("split", 2, 48, (256, 256, 256), "interpret"),
    "split_tiles_of_32_rows_unequal_widths":
        ("split", 1, 96, (512, 128, 128), "interpret"),
    "norm_two_groups_a_block_three_row_tiles":
        ("norm", 2, 48, 256, 2, "interpret"),
    "norm_one_group_of_two_lane_tiles_tiles_of_32_rows":
        ("norm", 1, 96, 256, 1, "interpret"),
}


def _case(name, dtype):
    """(the op, the loop, the operands differentiated, the cotangents, the
    key in `dispatch.taken()`, the path planned) of a line of SHAPES."""
    kind, b, t, *rest = SHAPES[name][:-1]
    if kind == "l2norm":
        key_heads, d_k, values = rest
        qkv, conv_w, cotangents = _operands(b, t, key_heads, d_k, values,
                                            dtype)
        q_scale = 1.0 / math.sqrt(d_k)
        return (lambda *o: mc.conv_silu_l2norm(*o, key_heads, d_k, q_scale),
                lambda *o: _loop(*o, key_heads, d_k, q_scale),
                (qkv, conv_w), cotangents, "mixer_chain",
                mc.path(qkv, conv_w, key_heads, d_k))
    rng = np.random.default_rng(t)
    if kind == "split":
        widths, = rest
        channels = sum(widths)
        x = jnp.asarray(rng.standard_normal((b, t, channels)), dtype)
        conv_w = jnp.asarray(0.5 * rng.standard_normal((TAPS, channels)), F32)
        conv_b = jnp.asarray(0.5 * rng.standard_normal((channels,)), F32)
        return (lambda *o: mc.conv_silu_split(*o, widths),
                lambda *o: _split_loop(*o, widths), (x, conv_w, conv_b),
                [jnp.asarray(rng.standard_normal((b, t, n)), F32)
                 for n in widths], "ssd_chain",
                mc.split_path(x, conv_w, widths))
    columns, groups = rest
    y, z, cotangent = (jnp.asarray(rng.standard_normal((b, t, columns)), d)
                       for d in (dtype, dtype, F32))
    w = jnp.asarray(1.0 + 0.3 * rng.standard_normal((columns,)), F32)
    return (lambda *o: (mc.gated_group_norm(*o, groups, EPS),),
            lambda *o: (_norm_loop(*o, groups),), (y, z, w), [cotangent],
            "ssd_chain", mc.norm_path(y, groups))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_chain_and_its_gradients_match_a_float32_loop(name, dtype, path,
                                                      monkeypatch):
    op, loop, operands, cotangents, key, planned = _case(name, dtype)
    monkeypatch.setattr(dispatch, "_taken", {})
    want_path = SHAPES[name][-1] if path == "interpret" else "xla"
    assert planned == want_path
    got, got_g = _with_gradients(op, cotangents)(*operands)
    assert dispatch.taken()[key] == {want_path: 1}
    want, want_g = _with_gradients(loop, cotangents)(*operands)
    # a kernel rounds once, on the way out (as the XLA formulation of the
    # chain with a bias does); the linear mixer's XLA formulation multiplies
    # the taps and takes SiLU in qkv's dtype, the gated norm's rounds the
    # gated product before the norm
    kernel = want_path == "interpret"
    tol = (1e-5 if dtype == jnp.float32
           else 2 ** -8 if kernel else 2 ** -5)
    for g, w, c in zip(got, want, cotangents):
        assert g.shape == c.shape and g.dtype == dtype
        np.testing.assert_allclose(np.asarray(g.astype(F32)), np.asarray(w),
                                   rtol=tol, atol=tol)
    assert [g.dtype for g in got_g] == [o.dtype for o in operands]
    for g, w in zip(got_g, want_g):
        scale = float(jnp.abs(w.astype(F32)).max())
        np.testing.assert_allclose(
            np.asarray(g.astype(F32)), np.asarray(w.astype(F32)),
            rtol=tol, atol=(tol if dtype == jnp.float32 or kernel
                            else 2 ** -3) * scale)


# the last array of each chain with a convolution, at three lane tiles of
# channels, and the loop's: what the two tests below walk
LAST_ARRAY = {
    "l2norm": (lambda x, conv_w, conv_b: mc.conv_silu_l2norm(
        x, conv_w, 1, 128, 1.0)[2],
               lambda x, conv_w, conv_b: _loop(x, conv_w, 1, 128, 1.0)[2],
               0.0),
    "split": (lambda x, conv_w, conv_b: mc.conv_silu_split(
        x, conv_w, conv_b, (128, 128, 128))[2],
              lambda x, conv_w, conv_b: _split_loop(
                  x, conv_w, conv_b, (128, 128, 128))[2],
              0.5),
}


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


def test_position_0_of_the_chain_with_a_bias_and_the_norm_row_by_row(path):
    """As above for x, B, C, whose position 0 is the bias and the taps'
    last columns; and a row of the gated norm moves with no other row."""
    widths = (256, 256, 256)
    x, conv_w, _ = _operands(3, 32, 2, 128, 256, F32)
    conv_b = jnp.linspace(-1.0, 1.0, 768)
    other = x.at[0].set(7.0).at[2].set(-3.0)
    got, moved = (mc.conv_silu_split(v, conv_w, conv_b, widths)
                  for v in (x, other))
    for g, m in zip(got, moved):
        np.testing.assert_array_equal(np.asarray(g[1]), np.asarray(m[1]))
    alone = x[:, 0] * conv_w[TAPS - 1] + conv_b
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([g[:, 0] for g in got], -1)),
        np.asarray(alone / (1.0 + jnp.exp(-alone))), rtol=1e-5, atol=1e-6)
    y, z, w = x[..., :256], x[..., 256:512], conv_b[:256]
    got, moved = (mc.gated_group_norm(v, z, w, 2, EPS)
                  for v in (y, y.at[0].set(7.0).at[1, 5].set(2.0)))
    same = np.ones((3, 32), bool)
    same[0], same[1, 5] = False, False
    np.testing.assert_array_equal(np.asarray(got)[same],
                                  np.asarray(moved)[same])


@pytest.mark.parametrize("position", [13, 14, 15, 16, 17, 18])
@pytest.mark.parametrize("op", sorted(LAST_ARRAY))
def test_a_tile_boundary_passes_values_forward_and_cotangents_back(
        op, position, monkeypatch):
    """One position changed three either side of the boundary between the
    row tiles 0-15 and 16-31: the last array moves at that position and the
    three behind it and nowhere else, and the cotangent of one of its rows
    reaches that row of the operand and the three before it, as the
    loop's does."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    x, conv_w, _ = _operands(1, 48, 1, 128, 128, F32)
    kernel, loop, bias = LAST_ARRAY[op]
    conv_b = jnp.full((384,), bias)
    assert mc._plan(x, 1, 128)[0] == 16                 # three row tiles
    assert mc._split_plan(x, (128, 128, 128))[0] == 16

    @jax.jit
    def v_of(x):
        return kernel(x, conv_w, conv_b)

    moved = np.asarray(jnp.abs(v_of(x.at[0, position].add(1.0))
                               - v_of(x)).max(-1)[0]) > 0
    assert list(np.flatnonzero(moved)) == list(range(position,
                                                     position + TAPS))
    grad = jax.jit(jax.grad(lambda x: jnp.sum(v_of(x)[0, position])))(x)
    want = jax.jit(jax.grad(lambda x: jnp.sum(
        loop(x, conv_w, conv_b)[0, position])))(x)
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


def test_each_way_off_the_mamba_passes_shapes_goes_to_xla(monkeypatch):
    """A width that is no whole lane tile, rows no multiple of 16, ten taps,
    a group of 64 columns, a mesh of two devices, a CPU without the
    interpreter: XLA's lines, and `dispatch.taken()` says so."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(dispatch, "_taken", {})
    x = jnp.ones((1, 32, 768), F32)
    conv_w, conv_b, widths = jnp.ones((TAPS, 768)), jnp.zeros(768), (256,) * 3
    y, w = x[..., :256], jnp.ones(256)
    assert mc.split_path(x, conv_w, widths) == "interpret"
    assert mc.norm_path(y, 2) == "interpret"
    assert mc.split_path(x, conv_w, (640, 64, 64)) == "xla"
    assert mc.split_path(x[:, :24], conv_w, widths) == "xla"
    assert mc.split_path(x, jnp.ones((10, 768)), widths) == "xla"
    assert mc.norm_path(y, 4) == "xla" and mc.norm_path(y[:, :24], 2) == "xla"
    for bad in ((256, 256), (256, 256, 128)):
        with pytest.raises(ValueError, match="widths"):
            mc.conv_silu_split(x, conv_w, conv_b, bad)
    with pytest.raises(ValueError, match="for y"):
        mc.gated_group_norm(y, y[:, :16], w, 2, EPS)
    assert len(jax.devices()) > 1       # conftest.py's virtual devices
    with jax.sharding.set_mesh(jax.sharding.Mesh(jax.devices()[:2],
                                                 ("fsdp",))):
        assert mc.split_path(x, conv_w, widths) == "xla"
        assert mc.norm_path(y, 2) == "xla"
    got = mc.conv_silu_split(x[:, :24], conv_w, conv_b, widths)
    want = _split_loop(x[:, :24], conv_w, conv_b, widths)
    for g, v in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mc.gated_group_norm(y, y, w, 4, EPS)),
        np.asarray(_norm_loop(y, y, w, 4)), rtol=1e-5, atol=1e-6)
    assert dispatch.taken()["ssd_chain"] == {"xla": 2}
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "")
    assert mc.split_path(x, conv_w, widths) == "xla"    # a CPU run
    assert mc.norm_path(y, 2) == "xla"
