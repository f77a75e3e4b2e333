"""ops/ssd_scan.py: the chunked XLA form and the kernels (interpreted on the
CPU) against `ssd_scan_reference`, the recurrence one step at a time:
forward and every operand's gradient, a sequence that is no multiple of the
chunk, one chunk, one longer than a block of chunks (the state and dL/dS
carried from grid step to grid step), heads over groups at 8 : 1, 2 : 1 and
1 : 1, dt = 0 rows, decays that underflow, D's term, a float32 state under
bfloat16 operands, and the MXU passes a chunk the plan reports."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch, ssd_scan as ss


def _inputs(t, heads=4, groups=2, P=64, N=128, b=1, seed=0, dtype=jnp.float32,
            strong=False):
    ks = jax.random.split(jax.random.key(seed + t), 6)
    x = jax.random.normal(ks[0], (b, t, heads, P)).astype(dtype)
    # a step of 0.001 .. 0.1 beside a of -1 .. -16: a step keeps 0.2 to
    # 0.999 of the state (strong: a step of 1 .. 8, which a chunk's running
    # sum carries far under exp's underflow)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, t, heads),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    dt = dt * 80.0 if strong else dt
    a = -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0)
    B = (jax.random.normal(ks[3], (b, t, groups, N)) / np.sqrt(N)
         ).astype(dtype)
    C = jax.random.normal(ks[4], (b, t, groups, N)).astype(dtype)
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (heads,))
    return x, dt, a, B, C, D


def _rel(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want ** 2)))


def _scan(path, monkeypatch):
    if path == "xla":
        return ss.ssd_scan_xla
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    return ss.ssd_scan


# 128: one chunk; 200: padded to 256; 1100: nine chunks, padded to two
# blocks of 8; heads over groups 2 : 1, 8 : 1 (the cell's) and 1 : 1 (a head
# of 128: one head a lane tile)
@pytest.mark.parametrize("path,t,heads,groups,P", [
    ("xla", 200, 4, 2, 64), ("xla", 200, 3, 3, 16),
    ("kernels", 128, 4, 2, 64), ("kernels", 200, 4, 2, 64),
    ("kernels", 200, 8, 1, 64), ("kernels", 130, 2, 2, 128),
    ("kernels", 1100, 2, 1, 64)])
def test_forward_and_gradients_match_the_recurrence(path, t, heads, groups, P,
                                                    monkeypatch):
    scan = _scan(path, monkeypatch)
    args = _inputs(t, heads, groups, P)
    want = ss.ssd_scan_reference(*args)
    got = scan(*args)
    assert got.shape == want.shape and got.dtype == args[0].dtype
    assert _rel(got, want) < 2e-5
    w = jax.random.normal(jax.random.key(7), want.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    every = tuple(range(6))
    for name, g, r in zip(("x", "dt", "a", "B", "C", "D"),
                          jax.jit(jax.grad(loss(scan), every))(*args),
                          jax.jit(jax.grad(loss(ss.ssd_scan_reference),
                                           every))(*args)):
        assert g.shape == r.shape and _rel(g, r) < 2e-4, name


def test_the_kernels_are_taken_where_the_shapes_allow(monkeypatch):
    """The header's rule: P a fraction of a lane tile, a group's heads whole
    lane tiles, N whole lane tiles; anything else takes the XLA lines."""
    assert ss.takes_kernels(64, 8, 64, 128)         # the cell's
    assert ss.takes_kernels(2, 2, 128, 128)
    assert not ss.takes_kernels(3, 3, 16, 128)      # a group is 16 lanes
    assert not ss.takes_kernels(4, 2, 64, 64)       # half a tile of state
    assert not ss.takes_kernels(4, 2, 96, 128)      # 96 divides no tile
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(dispatch, "_taken", {})
    ss.ssd_scan(*_inputs(64, 3, 3, 16))
    assert dispatch.taken()["ssd_scan"] == {"xla": 1}
    ss.ssd_scan(*_inputs(64, 8, 1, 64))
    assert dispatch.taken()["ssd_scan"] == {"xla": 1, "interpret": 1}
    plan, = dispatch.taken()["ssd_scan.plan"]
    assert plan.startswith("chunk128,heads8over1,p64,n128,state_f32,"
                           "bwd_pallas,passes")


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_a_step_of_zero_leaves_the_state_and_d_is_felt(path, monkeypatch):
    """dt = 0 at a step: the state passes through it untouched, so what
    follows reads the same as with the step cut out; the step's own y is
    the state's reading plus D x."""
    scan = _scan(path, monkeypatch)
    x, dt, a, B, C, D = _inputs(160)
    dt = dt.at[:, 40:60].set(0.0)
    y = scan(x, dt, a, B, C, D)
    cut = [v[:, np.r_[0:40, 60:160]] for v in (x, dt, B, C)]
    y_cut = scan(cut[0], cut[1], a, cut[2], cut[3], D)
    assert _rel(y[:, 60:], y_cut[:, 40:]) < 2e-5
    no_d = scan(x, dt, a, B, C, jnp.zeros_like(D))
    np.testing.assert_allclose(
        np.asarray(y - no_d), np.asarray(D[:, None] * x), atol=2e-5)
    assert _rel(no_d, y) > 0.1


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_decays_that_underflow_give_zero_and_not_nan(path, monkeypatch):
    """A long row of strong steps: a running sum passes -1e3, whose
    exp is 0; every decay is an exp of a difference <= 0, so nothing is
    inf / inf."""
    scan = _scan(path, monkeypatch)
    args = _inputs(384, strong=True)
    assert float(jnp.min(jnp.cumsum(args[1] * args[2], axis=1))) < -1e3
    got = scan(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, ss.ssd_scan_reference(*args)) < 2e-5
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a) ** 2),
                             (0, 1, 2, 3, 4)))(*args)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_bfloat16_operands_keep_a_float32_state(monkeypatch):
    """bfloat16 x, B and C go to the MXU as they are; the state, the decays
    and the masked product's left operand stay float32 (two bfloat16 parts):
    against the reference ON THE SAME ROUNDED OPERANDS the kernels are as
    near as in float32, up to y's own rounding."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = _inputs(520, 8, 1, 64, dtype=jnp.bfloat16)
    want = ss.ssd_scan_reference(*args)
    got = ss.ssd_scan(*args)
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < 4e-3           # one rounding of y: 2^-9
    # a state rounded to bfloat16 at every chunk's edge reads ten times that
    monkeypatch.setattr(ss, "_state_after", lambda *a: _rounded(
        _STATE_AFTER(*a)))
    ss._scan_fwd.clear_cache()
    low = ss.ssd_scan(*(v.astype(jnp.float32) for v in args))
    ss._scan_fwd.clear_cache()
    assert _rel(low, want) > 10 * _rel(
        ss.ssd_scan_xla(*(v.astype(jnp.float32) for v in args)), want)


_STATE_AFTER = ss._state_after


def _rounded(S):
    return S.astype(jnp.bfloat16).astype(jnp.float32)


def test_a_chunk_s_mxu_passes_at_the_cell_s_shapes():
    """The plan's `passes<fwd>+<bwd>` a chunk a head, counted from the traced
    chunk lines: with bfloat16 operands the forward is C B^T (1), a head's
    masked product (float32 left operand: 2) and the two products with the
    float32 state (2 + 2 a GROUP), 21 / 8 a head."""
    fwd, bwd = ss.mxu_passes(128, 8, 64, 128, jnp.dtype(jnp.bfloat16))
    assert fwd == 21 / 8
    assert 4 < bwd < 8
    f32, _ = ss.mxu_passes(128, 8, 64, 128, jnp.dtype(jnp.float32))
    assert f32 > fwd                    # the probe's operands: more parts
