"""Flash attention kernels vs reference (CPU interpret mode): values and
gradients at every plan a caller sends, the folded scale, the plan record.

Mirrors the reference's kernel-test strategy (colocated unit tests with
ground-truth comparisons, SURVEY.md §4 tier a).  The file's other parts:
tests/test_ops_attention_backward.py (dq out of the one backward pass),
tests/test_ops_attention_chunk.py (a chunk's offsets, as ring attention
calls it), tests/test_ops_attention_window.py (sliding windows, values of
another width) and tests/test_ops_attention_rope.py (rope inside the
kernels); tests/attention_cases.py holds what they share.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn
from attention_cases import (  # noqa: F401 (the fixture is autouse)
    _grads_and_value, _interpret_mode, _new_plans, _rand_qkv)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    b, s, h, d = 2, 256, 4, 64
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)

    ref = attn.attention_reference(q, k, v, causal=causal)
    out = attn.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_grads_match_reference():
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    b, s, h, d = 1, 128, 2, 64
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(attn.flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attn.attention_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4)


def test_cross_attention_shapes():
    """seq_q != seq_k (decode/cross-attn shape)."""
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 128, 2, 64), jnp.float32)
    k = jax.random.normal(kk, (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(kv, (1, 256, 2, 64), jnp.float32)
    ref = attn.attention_reference(q, k, v, causal=False)
    out = attn.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_fallback_on_odd_shapes():
    """Non-tile-divisible seq falls back to the reference path."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 100, 2, 32), jnp.float32)
    out = attn.flash_attention(q, q, q, causal=True)
    ref = attn.attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_backward_never_materializes_s_by_s():
    """The VERDICT round-2 bar: a long-sequence train step must not
    materialize the s×s score matrix in fwd OR bwd.  Trace the full
    value-and-grad jaxpr at seq 8192 and assert no intermediate is
    score-matrix sized (the old jnp backward produced [b,h,s,s] —
    256 MB/head-batch at this length)."""
    b, s, h, d = 1, 8192, 2, 64
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(attn.flash_attention(q, k, v, causal=True) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)

    def all_avals(jpr, acc):
        for eqn in jpr.eqns:
            for var in eqn.outvars:
                acc.append(var.aval)
            for val in eqn.params.values():
                if hasattr(val, "jaxpr"):  # nested (pallas kernels etc.)
                    all_avals(val.jaxpr, acc)
        return acc

    score_elems = s * s
    for aval in all_avals(jaxpr.jaxpr, []):
        if hasattr(aval, "shape") and aval.shape:
            elems = int(np.prod(aval.shape))
            assert elems < score_elems, (
                f"intermediate of shape {aval.shape} is score-matrix "
                "sized — flash backward must recompute by block")


# ---------------------------------------------------------------------------
# The kernels' plan (two loops, folded scale, block sizes): every shape a
# caller sends, against the reference, values and all three gradients.
# ---------------------------------------------------------------------------


# (sq, sk, block_q, block_k); None, None is `default_blocks`' own plan.
# sq < sk is end-aligned, and (128, 320, 128, 64) puts the diagonal 192
# rows in, off the q-block grid.  block_q = R * block_k narrows the
# forward's steps on the diagonal, block_k = R * block_q the backward's.
# sk // block_k is the number of key tiles the backward sums dq over.
_SHAPES = [(256, 256, 128, 128), (512, 512, 256, 256), (512, 512, 512, 512),
           (512, 512, 256, 128), (512, 512, 128, 256), (128, 384, 128, 128),
           (128, 320, 128, 64), (512, 512, 512, 128), (512, 512, 128, 512),
           (256, 768, 256, 128), (1024, 1024, None, None),
           (256, 1024, None, None)]


@pytest.mark.parametrize("sq,sk,bq,bk", _SHAPES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])     # scale folded / kept per score
def test_plan_values_and_grads_match_reference(d, causal, sq, sk, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(sq + sk + (bq or 0) + (bk or 0) + d), 4)
    q = jax.random.normal(ks[0], (1, sq, 2, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, 2, d), jnp.float32)
    w = jax.random.normal(ks[3], (1, sq, 2, d), jnp.float32)
    assert attn._scale_is_exact(d ** -0.5) == (d == 64)

    out, grads = _grads_and_value(
        lambda q, k, v: attn.flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk), q, k, v, w)
    ref, ref_grads = _grads_and_value(
        lambda q, k, v: attn.attention_reference(q, k, v, causal=causal),
        q, k, v, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_folded_scale_is_bit_for_bit_the_per_score_scale(causal, dtype):
    """Head size 64: 0.125 is a power of two, so scaling the q tile once
    gives the very scores that scaling each of them gives."""
    q, k, v = (x.astype(dtype) for x in _rand_qkv(7, 1, 512, 2, 64))
    offs = jnp.zeros((2,), jnp.int32)
    folded = attn._flash_fwd(q, k, v, offs, causal, 0.125, 256, 128,
                             fold_scale=True)
    kept = attn._flash_fwd(q, k, v, offs, causal, 0.125, 256, 128,
                           fold_scale=False)
    for a, b_ in zip(folded, kept):
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b_.astype(jnp.float32)))


@pytest.mark.parametrize("scale,exact", [
    (0.125, True), (64 ** -0.5, True), (0.25, True), (1.0, True),
    (128 ** -0.5, False), (0.1, False), (0.0, False)])
def test_scale_is_folded_only_when_a_power_of_two(scale, exact):
    assert attn._scale_is_exact(scale) is exact


@pytest.mark.parametrize("bq,bk,delta,dead", [
    (512, 512, 0, 1 - 2098176 / (10 * 512 * 512)),
    (2048, 256, 0, 1 - 2098176 / (256 * sum(2048 - 256 * t
                                            for t in range(8)))),
    (128, 128, 0, 1 - 2098176 / (136 * 128 * 128)),
    (1024, 256, 5000, 0.0),              # a chunk from the past
    (1024, 256, -5000, 0.0),             # from the future: nothing runs
    (512, 128, 64, None), (256, 512, 0, None), (128, 128, -100, None)])
def test_dead_share_counts_what_the_forward_computes(bq, bk, delta, dead):
    """The plan record's share of computed scores above the diagonal, at
    sequence 2048: known cases, and bounds off the block grid."""
    got = attn._dead_share(1000 + delta, 1000, 2048, 2048, bq, bk)
    if dead is None:
        assert 0.0 <= got < 0.6
    else:
        assert got == pytest.approx(dead, abs=1e-9)


@pytest.mark.parametrize("sq,sk,plan", [
    (2048, 2048, ((2048, 512), (512, 2048))),
    (1024, 1024, ((1024, 512), (512, 1024))),
    (256, 2048, ((256, 512), (256, 2048))),
    (4096, 4096, ((2048, 512), (512, 2048))),
    (1536, 1536, ((512, 512), (512, 512))),
    (128, 128, ((128, 128), (128, 128))),
    (128, 320, ((128, 320), (128, 320))),
    (100, 100, ((100, 100), (100, 100)))])
def test_default_blocks(sq, sk, plan):
    """(forward, backward): the forward tiles the queries, the backward
    the keys."""
    got = attn.default_blocks(64, sq, sk, jnp.bfloat16)
    assert got == plan
    for bq, bk in got:
        assert sq % bq == 0 and sk % bk == 0


def test_plan_is_recorded_beside_the_path():
    from ray_tpu.ops import dispatch

    q, k, v = _rand_qkv(8, 1, 2048, 1, 64)
    before = dispatch.taken()
    jax.make_jaxpr(lambda q, k, v: attn.flash_attention(q, k, v))(q, k, v)
    new = _new_plans(before)
    assert new["flash_attention"] == {"interpret": 1}
    assert new["flash_attention.plan"] == {
        "fwd2048x512,bwd512x2048,dq_in_pass,scale_folded,dead20/20%,"
        "operands_bshd,heads2x64": 1}
    # a traced offset (ring attention) and head size 128
    q, k, v = _rand_qkv(9, 1, 128, 1, 128)
    jax.make_jaxpr(lambda q, k, v, o: attn.flash_attention_chunk(
        q, k, v, o, 0))(q, k, v, jnp.int32(0))
    plans = dispatch.taken()["flash_attention.plan"]
    assert plans.get("fwd128x128,bwd128x128,dq_in_pass,scale_per_score,"
                     "dead_by_offset,operands_bshd,heads1x128")
