"""ops/gated_delta.py: the chunked XLA form and the kernels (interpreted on
the CPU) against `gated_delta_reference`, the recurrence one step at a time:
forward and all five gradients, 2 key / 4 value heads, strong and weak
decay, beta at 0 and at 1, chunk edges inside the sequence, a sequence that
is no multiple of the chunk and one longer than a block of chunks (the state
and dL/dS carried from grid step to grid step); one case at the cell's chunk
and widths; the exact inverse; and the MXU passes a chunk of the kernels'
traced bodies, which the plan reports."""

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
    grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                              argnums=(0, 1, 2, 3, 4)))(*args)
             for f in (rule, gd.gated_delta_reference)]
    for name, mine, ref in zip("q k v g beta".split(), *grads):
        assert mine.shape == ref.shape, name
        assert _rel(mine, ref) < 5e-5, name


def test_the_cell_s_chunk_and_widths_match_the_recurrence(monkeypatch):
    """Chunk 64, one key head over its two value heads of 128 (one program
    works both), bfloat16-valued float32 operands as the probe hands them:
    the row stacks of 128 form, and at 2 x 512 + 64 steps dL/dS and what
    the backward keeps in VMEM (T, D, K S, M, the decays) cross a block's
    edge and a padded tail."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = _inputs(2 * 512 + 64, True, hk=1, hv=2, dk=128, dv=128)
    want = gd.gated_delta_reference(*args)
    got = gd.gated_delta_rule(*args)
    assert _rel(got, want) < 3e-5
    w = jax.random.normal(jax.random.key(9), want.shape)
    grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                              argnums=(0, 1, 2, 3, 4)))(*args)
             for f in (gd.gated_delta_rule, gd.gated_delta_reference)]
    for name, mine, ref in zip("q k v g beta".split(), *grads):
        assert _rel(mine, ref) < 5e-5, name
    assert any(p.startswith("chunk64,heads2over1,dk128,dv128,state_f32,"
                            "bwd_pallas,passes")
               for p in dispatch.taken()["gated_delta_rule.plan"])


def _group_sum_of_the_view(d, value_heads, key_heads):
    """The sum as PR 42 wrote it: over the group axis of the [b, t, key
    heads, group, d_k] view."""
    b, t, wide = d.shape
    return jnp.sum(d.reshape(b, t, key_heads, value_heads // key_heads, -1),
                   axis=3, dtype=jnp.float32).reshape(
        b, t, wide * key_heads // value_heads).astype(d.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,hv,hk,dk", [
    (64, 4, 2, 128),        # whole tiles of 8 rows x 128 columns
    (24, 8, 2, 16),         # a group of four, heads narrower than a tile
    (13, 6, 3, 32),         # rows that fill no tile: tiles of one row
    (16, 2, 2, 128),        # a group of one: nothing to sum
])
def test_the_group_sum_by_tiles_is_the_sum_of_the_view(t, hv, hk, dk, dtype):
    d = jax.random.normal(jax.random.key(t + hv), (2, t, hv * dk)).astype(
        dtype)
    got = gd.over_group(d, hv, hk)
    want = _group_sum_of_the_view(d, hv, hk)
    assert got.shape == (2, t, hk * dk) and got.dtype == dtype
    # float32 sums in the group's order on both sides, one rounding
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


def test_the_rule_s_gradients_of_q_and_k_are_the_view_s(monkeypatch):
    """dq and dk of the kernels' path with the group summed by tiles equal,
    to the bit, what the sum over the view gives."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args = _inputs(80, True)
    w = jax.random.normal(jax.random.key(9), args[2].shape)

    def grads():
        return jax.grad(lambda *a: jnp.sum(
            gd.gated_delta_rule(*a, chunk=CHUNK) * w), argnums=(0, 1))(*args)

    by_tiles = grads()
    monkeypatch.setattr(gd, "over_group", _group_sum_of_the_view)
    for mine, theirs in zip(by_tiles, grads()):
        assert float(jnp.abs(theirs).max()) > 0
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))


def _dots(f, *args):
    return gd._count_dots(jax.make_jaxpr(f)(*args).jaxpr)


@pytest.mark.parametrize("kernel,ceiling,parent", [
    ("forward", 32, 44), ("backward", 70, 118)])
def test_a_chunk_s_mxu_passes_at_the_cell_s_shapes(kernel, ceiling, parent,
                                                   monkeypatch):
    """The dot_generals of a kernel's traced body a chunk a value head at
    chunk 64, d_k = d_v = 128, bfloat16 q / k / v, two value heads a
    program (the parent's: 44 and 118), counted here from the lines the
    kernel runs; the plan string says the same numbers."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    sds = jax.ShapeDtypeStruct
    qk, v, row, S = (sds((64, 128), bf16), sds((64, 128), bf16),
                     sds((1, 64), f32), sds((128, 128), f32))

    def walk(q):        # both heads of a program, K K^T (and Q K^T) once
        def f(q_, k, v, rows, S):
            return [(c.get("o"), S) for c, S in gd._heads_walk(
                q_ if q else None, k, [v, v], rows, rows, [S, S])]
        return _dots(f, qk, qk, v, sds((2, 1, 64), f32), S) / 2

    if kernel == "forward":
        counted = walk(True)
    else:               # its walk forward (no o), then its walk back
        kept = gd._abstract_chunk(64, 128, 128, bf16, bf16)[1]
        counted = walk(False) + _dots(gd._chunk_backward, qk, qk, v, row, row,
                                      S, kept, v, S)
    assert counted <= ceiling < parent
    passes = gd.mxu_passes(64, 128, 128, jnp.dtype(bf16), jnp.dtype(bf16), 2)
    assert dict(zip(("forward", "backward"), passes))[kernel] == counted
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(dispatch, "_taken", {})
    shapes = [sds((1, 64, 1, 128), bf16)] * 2 + [
        sds((1, 64, 2, 128), bf16)] + [sds((1, 64, 2), f32)] * 2
    jax.eval_shape(lambda *a: gd.gated_delta_rule(*a), *shapes)
    plan, = dispatch.taken()["gated_delta_rule.plan"]
    assert plan.endswith(f",bwd_pallas,passes{passes[0]:g}+{passes[1]:g}")


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
    assert any(p.startswith(f"chunk{CHUNK},heads4over2,dk16,dv32,state_f32,"
                            "bwd_pallas,passes") for p in plans)


def test_the_nilpotent_product_is_the_inverse():
    """(I + A)^-1 of a strictly lower triangular A by the product (I - A)
    (I + A^2)(I + A^4) .. against a triangular solve: exact to rounding,
    also where A's entries are as large as the rule's can be; at n 64 six
    float32 products, each three MXU passes (the parent's: ten)."""
    for n, scale in ((16, 1.0), (64, 0.1), (64, 0.3)):
        a = jnp.tril(scale * jax.random.normal(jax.random.key(n), (n, n)),
                     -1)
        want = jax.scipy.linalg.solve_triangular(
            jnp.eye(n) + a, jnp.eye(n), lower=True)
        got = gd._unit_lower_inverse(a)
        assert _rel(got, want) < 1e-4, (n, scale)
        assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    assert _dots(gd._unit_lower_inverse, a) == 6 * 3
