"""Flash attention's backward (CPU interpret mode): dq out of the backward's
one pass, stored where a head has one key tile, summed in float32 over the
key-tile axis where it has several.  A part of tests/test_ops_attention.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn
from attention_cases import (  # noqa: F401 (the fixture is autouse)
    _grads_and_value, _interpret_mode, _masked_reference, _pallas_calls)


# causal, window: causal, not causal, a window off the block grid
_MASKS = [(True, None), (False, None), (True, 200)]


@pytest.mark.parametrize("key_tiles", [1, 2, 4])
@pytest.mark.parametrize("causal,window", _MASKS)
@pytest.mark.parametrize("d", [64, 128])     # scale folded / kept per score
def test_dq_from_the_one_pass_matches_reference(d, causal, window,
                                                key_tiles, monkeypatch):
    """dq, dk and dv of the fused backward at 1, 2 and 4 key tiles, the
    query block a quarter of the longest tile (so the diagonal's narrow
    steps run); the plan record says over how many tiles dq was summed."""
    monkeypatch.setattr(attn.dispatch, "_taken", {})
    sq = sk = 512
    bq, bk = 128, sk // key_tiles
    ks = jax.random.split(jax.random.PRNGKey(31 + d + key_tiles), 4)
    q, k, v, w = (jax.random.normal(x, (1, sq, 2, d), jnp.float32)
                  for x in ks)
    out, grads = _grads_and_value(
        lambda q, k, v: attn.flash_attention(
            q, k, v, causal=causal, window=window, block_q=bq, block_k=bk),
        q, k, v, w)
    ref, ref_grads = _grads_and_value(
        _masked_reference(sq, sk, d, causal, window), q, k, v, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)
    (plan, _), = attn.dispatch.taken()["flash_attention.plan"].items()
    assert f"bwd{bq}x{bk},dq_in_pass," in plan
    assert (f",dq_over{key_tiles}tiles," in plan) == (key_tiles > 1)


@pytest.mark.parametrize("key_tiles", [1, 2, 4])
@pytest.mark.parametrize("d", [64, 128])
def test_dq_with_fewer_queries_than_keys_off_the_block_grid(d, key_tiles):
    """sq < sk end-aligned with the diagonal 192 rows in, off the query
    blocks: some key tiles meet no query block whole, and the last meets
    them all."""
    sq, bq, sk = 128, 64, {1: 320, 2: 384, 4: 512}[key_tiles]
    bk = sk // key_tiles
    ks = jax.random.split(jax.random.PRNGKey(77 + d + key_tiles), 4)
    q, w = (jax.random.normal(x, (1, sq, 2, d), jnp.float32)
            for x in ks[:2])
    k, v = (jax.random.normal(x, (1, sk, 2, d), jnp.float32)
            for x in ks[2:])
    _, grads = _grads_and_value(
        lambda q, k, v: attn.flash_attention(q, k, v, block_q=bq,
                                             block_k=bk), q, k, v, w)
    _, ref_grads = _grads_and_value(
        _masked_reference(sq, sk, d, True, None), q, k, v, w)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("d", [64, 128])
def test_dq_over_four_key_tiles_is_one_float32_sum_rounded_once(d):
    """With float32 inputs dq summed over 4 key tiles equals dq from 1 key
    tile to 2e-5 relative: the sum over the key-tile axis is kept in
    float32.  A running sum rounded to bfloat16 after each tile (a relative
    1 / 256 each time) fails this by two orders."""
    sq = sk = 512
    ks = jax.random.split(jax.random.PRNGKey(5 + d), 4)
    q, k, v, w = (jax.random.normal(x, (1, sq, 1, d), jnp.float32)
                  for x in ks)

    def dq(block_k):
        return np.asarray(_grads_and_value(
            lambda q, k, v: attn.flash_attention(q, k, v, block_q=128,
                                                 block_k=block_k),
            q, k, v, w)[1][0])

    one, four = dq(512), dq(128)
    scale = np.abs(one).max()
    assert np.abs(four - one).max() <= 2e-5 * scale
    # the control: what rounding the running sum to bfloat16 would do
    rounded = np.asarray(jnp.asarray(one).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    assert np.abs(rounded - one).max() > 1e-3 * scale


@pytest.mark.parametrize("key_tiles", [1, 4])
def test_one_backward_kernel_gives_dq_dk_dv_in_the_operands_dtype(key_tiles):
    """bfloat16 operands: forward and ONE backward pallas_call, whose three
    results leave it in the operands' dtype whether dq was summed over one
    key tile or four (the float32 sum is the kernel's scratch; nothing is
    left for XLA to round)."""
    sq = sk = 512
    x = jax.ShapeDtypeStruct((1, sq, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return attn.flash_attention(
            q, k, v, block_q=128,
            block_k=sk // key_tiles).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    calls = _pallas_calls(jaxpr.jaxpr)
    backward = [c for c in calls if len(c.outvars) == 3]
    assert len(calls) == 2 and len(backward) == 1, calls
    for out, seq in zip(backward[0].outvars, (sq, sk, sk)):
        assert out.aval.dtype == jnp.bfloat16
        # as the projections' gradients read them: two heads of 64 wide
        assert out.aval.shape == (1, seq, 2 * 64)
