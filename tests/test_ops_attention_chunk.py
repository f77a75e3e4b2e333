"""`flash_attention_chunk` (CPU interpret mode): a chunk at global offsets, as
ring attention calls it, at every position against the diagonal; out, lse and
the gradients, a cotangent on lse included.  A part of
tests/test_ops_attention.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn
from attention_cases import (  # noqa: F401 (the fixture is autouse)
    _interpret_mode, _rand_qkv)


@pytest.mark.parametrize("kv_off,label", [(0, "past"), (256, "diagonal"),
                                          (384, "future")])
def test_chunk_offsets_match_masked_reference(kv_off, label):
    """flash_attention_chunk with global offsets == explicit-mask chunk
    attention, for each ring-step shape (fully visible / diagonal /
    fully masked)."""
    from ray_tpu.ops import ring_attention as ring

    b, s, h, d = 1, 128, 2, 64
    q, k, v = _rand_qkv(4, b, s, h, d)
    out, lse = attn.flash_attention_chunk(
        q, k, v, 256, kv_off, causal=True, block_q=64, block_k=64)
    qpos = 256 + jnp.arange(s)
    kpos = kv_off + jnp.arange(s)
    mask = (qpos[:, None] >= kpos[None, :])[None, None]
    o_ref, lse_ref = ring._chunk_attention(q, k, v, mask, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    lse = lse.reshape(b, h, s)
    masked = np.asarray(lse_ref) < -1e29
    assert (np.asarray(lse) < -1e29).tolist() == masked.tolist()
    np.testing.assert_allclose(np.asarray(lse)[~masked],
                               np.asarray(lse_ref)[~masked],
                               atol=2e-5, rtol=2e-5)


def test_chunk_lse_gradient_flows_through_merge():
    """Ring merges weight chunks by lse, so the chunk op's lse output
    must be differentiable: two merged flash chunks == one reference
    attention over the concatenated keys, gradients included."""
    from ray_tpu.ops import ring_attention as ring

    b, s, h, d = 1, 128, 2, 64
    q, k, v = _rand_qkv(5, b, s, h, d)

    def loss_merged(q, k, v):
        o1, l1 = attn.flash_attention_chunk(
            q, k, v, s, 0, causal=True, block_q=64, block_k=64)
        o2, l2 = attn.flash_attention_chunk(
            q, k, v, s, s, causal=True, block_q=64, block_k=64)
        o, _ = ring._merge(o1.astype(jnp.float32), l1.reshape(b, h, s),
                           o2.astype(jnp.float32), l2.reshape(b, h, s))
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        kk = jnp.concatenate([k, k], axis=1)
        vv = jnp.concatenate([v, v], axis=1)
        return jnp.sum(
            attn.attention_reference(q, kk, vv, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_merged, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)


# q_off - kv_off for 2 q-blocks of 128 over 4 k-blocks of 128: what the
# q-blocks' key ranges look like against the diagonal.
_DELTAS = [(600, "wholly past: the unmasked loop alone"),
           (0, "diagonal in the first block (q-block 0)"),
           (200, "diagonal in middle blocks, off the block grid"),
           (384, "diagonal in the last block; q-block 1 wholly past"),
           (-100, "rows before the chunk see nothing"),
           (-300, "wholly future: neither loop runs")]


@pytest.mark.parametrize("delta,what", _DELTAS)
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("d", [64, 128])
def test_chunk_positions_values_lse_and_grads(d, bq, bk, delta, what):
    """flash_attention_chunk at every position of a chunk against the
    diagonal, with a loss that reads out AND lse (nonzero dlse, as ring
    attention's merge gives): values, lse and dq, dk, dv against the
    explicit-mask reference."""
    from ray_tpu.ops import ring_attention as ring

    b, sq, sk, h = 1, 256, 512, 2
    ks = jax.random.split(jax.random.PRNGKey(1000 + delta + d), 5)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, h, d), jnp.float32)
    w = jax.random.normal(ks[3], (b, sq, h, d), jnp.float32)
    u = jax.random.normal(ks[4], (b, h, sq), jnp.float32)
    q_off, kv_off = 1000 + delta, 1000
    mask = ((q_off + jnp.arange(sq))[:, None]
            >= (kv_off + jnp.arange(sk))[None, :])[None, None]

    def flash(q, k, v):
        out, lse = attn.flash_attention_chunk(
            q, k, v, jnp.int32(q_off), jnp.int32(kv_off), causal=True,
            block_q=bq, block_k=bk)
        return out, lse.reshape(b, h, sq)

    def ref(q, k, v):
        return ring._chunk_attention(q, k, v, mask, d ** -0.5)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return (jnp.sum(out * w)
                    + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) * u))
        return f

    (out, lse), (o_ref, lse_ref) = flash(q, k, v), ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    hidden = np.asarray(lse_ref) < -1e29
    assert (np.asarray(lse) < -1e29).tolist() == hidden.tolist()
    assert not np.asarray(out)[hidden.transpose(0, 2, 1)].any()
    if delta == -300:
        assert hidden.all()
    np.testing.assert_allclose(np.asarray(lse)[~hidden],
                               np.asarray(lse_ref)[~hidden],
                               atol=2e-5, rtol=2e-5)
    g = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("delta,what", _DELTAS)
@pytest.mark.parametrize("key_tiles", [1, 4])
def test_chunk_dq_at_every_position_with_nonzero_dlse(key_tiles, delta, what):
    """flash_attention_chunk's dq (traced offsets, a loss that reads lse)
    where the chunk's keys are one tile and where they are four: a tile
    wholly in the future adds nothing, one wholly in the past its whole
    block, and dq is their sum."""
    from ray_tpu.ops import ring_attention as ring

    b, sq, sk, h, d = 1, 256, 512, 1, 64
    bq, bk = 128, sk // key_tiles
    ks = jax.random.split(jax.random.PRNGKey(2000 + delta), 5)
    q, w = (jax.random.normal(x, (b, sq, h, d), jnp.float32) for x in ks[:2])
    k, v = (jax.random.normal(x, (b, sk, h, d), jnp.float32) for x in ks[2:4])
    u = jax.random.normal(ks[4], (b, h, sq), jnp.float32)
    q_off, kv_off = 1000 + delta, 1000
    mask = ((q_off + jnp.arange(sq))[:, None]
            >= (kv_off + jnp.arange(sk))[None, :])[None, None]

    def flash(q, k, v, q_off, kv_off):
        out, lse = attn.flash_attention_chunk(
            q, k, v, q_off, kv_off, causal=True, block_q=bq, block_k=bk)
        return out, lse.reshape(b, h, sq)

    def loss(fn):
        def f(q, k, v, *offs):
            out, lse = fn(q, k, v, *offs)
            return (jnp.sum(out * w)
                    + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) * u))
        return f

    g = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(
        q, k, v, jnp.int32(q_off), jnp.int32(kv_off))
    g_ref = jax.jit(jax.grad(loss(lambda q, k, v: ring._chunk_attention(
        q, k, v, mask, d ** -0.5)), argnums=(0, 1, 2)))(q, k, v)
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)
    if delta == -300:
        assert not any(np.asarray(a).any() for a in g)
