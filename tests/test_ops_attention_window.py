"""Flash attention under a sliding window (query t sees keys s with 0 <= t -
s < window) and with values of another width than the keys (CPU interpret
mode).  A part of tests/test_ops_attention.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn
from attention_cases import (  # noqa: F401 (the fixture is autouse)
    _grads_and_value, _interpret_mode, _masked_reference, _rand_qkv)


# (sq, sk, window, block_q, block_k); None, None is `default_blocks`' plan
# (tile = block = 512 under a window).  (2048, 2048, 512) is the benchmark's
# window at a quarter of its sequence; the others put the window's trailing
# edge off the block grid, inside one block, over a long tile's narrow
# steps, and over end-aligned queries (sq < sk).
_WINDOWS = [(2048, 2048, 512, None, None), (1024, 1024, 300, 256, 256),
            (512, 512, 100, 256, 128), (1024, 1024, 512, 1024, 256),
            (512, 512, 130, 128, 512), (256, 768, 200, 128, 128),
            (512, 512, 1, 128, 128)]


@pytest.mark.parametrize("sq,sk,window,bq,bk", _WINDOWS)
@pytest.mark.parametrize("d", [64, 128])
def test_window_values_and_grads_match_masked_reference(d, sq, sk, window,
                                                        bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(sq + sk + window + d), 4)
    heads = 1 if sq >= 2048 else 2
    q = jax.random.normal(ks[0], (1, sq, heads, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, heads, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, heads, d), jnp.float32)
    w = jax.random.normal(ks[3], (1, sq, heads, d), jnp.float32)
    out, grads = _grads_and_value(
        lambda q, k, v: attn.flash_attention(
            q, k, v, window=window, block_q=bq, block_k=bk), q, k, v, w)
    ref, ref_grads = _grads_and_value(
        _masked_reference(sq, sk, d, True, window), q, k, v, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)
    # the XLA fallback takes the same window
    np.testing.assert_allclose(
        np.asarray(attn.attention_reference(q, k, v, window=window)),
        np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [512, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_window_at_least_the_sequence_is_bit_for_bit_the_causal_call(
        window, dtype, monkeypatch):
    """A window no query can reach the end of builds the causal kernels:
    the same values and gradients to the bit, and the causal plan."""
    monkeypatch.setattr(attn.dispatch, "_taken", {})
    q, k, v = (x.astype(dtype) for x in _rand_qkv(11, 1, 512, 2, 64))
    w = _rand_qkv(12, 1, 512, 2, 64)[0].astype(dtype)
    out_w, g_w = _grads_and_value(
        lambda q, k, v: attn.flash_attention(q, k, v, window=window),
        q, k, v, w)
    out_c, g_c = _grads_and_value(
        lambda q, k, v: attn.flash_attention(q, k, v), q, k, v, w)
    for a, b_ in zip((out_w, *g_w), (out_c, *g_c)):
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b_.astype(jnp.float32)))
    plans = attn.dispatch.taken()["flash_attention.plan"]
    assert len(plans) == 1 and "window" not in next(iter(plans))


@pytest.mark.parametrize("seq,window,blocks,visited,dead", [
    # a tile of 512 meets the block on its diagonal and the one behind it
    (8192, 512, None, (1 + 15 * 2) / 256, 0.5),
    (2048, 512, None, 7 / 16, 0.5),
    # a long tile meets every block of its window with all its queries
    (2048, 512, (2048, 512), 4 / 4, None),
    (2048, 100, (256, 256), (1 + 7 * 2) / 64, None)])
def test_window_plan_record(seq, window, blocks, visited, dead, monkeypatch):
    """`flash_attention.plan` carries the window and the share of (tile,
    block) pairs the forward visits; `default_blocks` drops the long tile
    under a window; `_dead_share` counts the scores behind the window."""
    monkeypatch.setattr(attn.dispatch, "_taken", {})
    bq, bk = blocks or (None, None)
    x = jax.ShapeDtypeStruct((1, seq, 1, 64), jnp.float32)
    jax.eval_shape(lambda q, k, v: attn.flash_attention(
        q, k, v, window=window, block_q=bq, block_k=bk), x, x, x)
    (plan, times), = attn.dispatch.taken()["flash_attention.plan"].items()
    assert times == 1 and f",window{window},visited" in plan
    assert plan.endswith(",operands_bshd,heads2x64")
    got = float(plan.rsplit("visited", 1)[1].split("%")[0]) / 100
    assert got == pytest.approx(visited, abs=6e-4)
    if blocks is None:
        assert attn.default_blocks(64, seq, seq, jnp.float32, window) == (
            (512, 512),) * 2
        assert plan.startswith(
            f"fwd512x512,bwd512x512,dq_in_pass,dq_over{seq // 512}tiles,")
    if dead is not None:
        assert attn._dead_share(0, 0, seq, seq, 512, 512, window) \
            == pytest.approx(dead, abs=2e-3)
        # without the window the same blocks waste less: only the diagonal
        assert attn._dead_share(0, 0, seq, seq, 512, 512) < dead
    with pytest.raises(ValueError):     # a window is causal
        attn._chunk(x, x, x, 0, 0, False, 0.125, ((512, 512),) * 2, window)


# ---------------------------------------------------------------------------
# Values of another width than the keys (PR 34: latent attention in training,
# keys 192 wide, values 128): the same two kernels, nothing padded.
# ---------------------------------------------------------------------------


# (d, e, sq, sk, block_q, block_k, causal, window)
_WIDTHS = [
    (192, 128, 256, 256, 128, 128, True, None),     # the cell's widths
    (192, 128, 512, 512, None, None, True, None),   # default_blocks' plan
    (192, 128, 512, 512, 256, 128, True, None),     # narrow forward steps
    (192, 128, 512, 512, 128, 256, True, None),     # narrow backward steps
    (192, 128, 128, 384, 128, 128, True, None),     # fewer queries than keys
    (192, 128, 256, 256, 128, 128, False, None),
    (192, 128, 512, 512, 128, 128, True, 192),      # under a window
    (64, 128, 256, 256, 128, 128, True, None),      # values the wider; folded
    (128, 64, 256, 512, 128, 256, True, None),
]


@pytest.mark.parametrize("d,e,sq,sk,bq,bk,causal,window", _WIDTHS)
def test_value_width_differs_values_and_grads_match_reference(
        d, e, sq, sk, bq, bk, causal, window):
    """out, dq, dk and dv of the Pallas kernels against
    `attention_reference` where v is e wide and q, k are d wide; dv comes
    out e wide, dq and dk d wide."""
    ks = jax.random.split(jax.random.PRNGKey(d + e + sq + sk), 4)
    q = jax.random.normal(ks[0], (1, sq, 2, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, 2, e), jnp.float32)
    w = jax.random.normal(ks[3], (1, sq, 2, e), jnp.float32)
    out, grads = _grads_and_value(
        lambda q, k, v: attn.flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk, window=window),
        q, k, v, w)
    ref, ref_grads = _grads_and_value(
        lambda q, k, v: attn.attention_reference(q, k, v, causal=causal,
                                                 window=window), q, k, v, w)
    assert out.shape == (1, sq, 2, e)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)


def test_value_width_is_seen_in_the_input_and_said_in_the_plan(monkeypatch):
    """Keys 192 / values 128 take the kernels (never the XLA path, never a
    padded v) and the plan says both widths; equal widths record the plan
    they always did, with no word about widths."""
    monkeypatch.setattr(attn.dispatch, "_taken", {})
    x = jnp.ones((1, 256, 2, 192), jnp.bfloat16)
    out = attn.flash_attention(x, x, x[..., :128], block_q=128, block_k=128)
    assert out.shape == (1, 256, 2, 128) and out.dtype == jnp.bfloat16
    taken = attn.dispatch.taken()
    assert taken["flash_attention"] == {"interpret": 1}
    assert list(taken["flash_attention.plan"]) == [
        "fwd128x128,bwd128x128,dq_in_pass,dq_over2tiles,scale_per_score,"
        "dead33/33%,dqk192,dv128,operands_bshd,heads2x192"]
    monkeypatch.setattr(attn.dispatch, "_taken", {})
    attn.flash_attention(x, x, x, block_q=128, block_k=128)
    assert list(attn.dispatch.taken()["flash_attention.plan"]) == [
        "fwd128x128,bwd128x128,dq_in_pass,dq_over2tiles,scale_per_score,"
        "dead33/33%,operands_bshd,heads2x192"]


def test_value_width_bfloat16_backward_gives_each_gradient_its_own_width():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 256, 2, 192), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 256, 2, 192), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 256, 2, 128), jnp.bfloat16)

    def loss(fn):
        return lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum()

    got = jax.jit(jax.grad(loss(lambda q, k, v: attn.flash_attention(
        q, k, v, block_q=128, block_k=128)), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(attn.attention_reference),
                            argnums=(0, 1, 2)))(q, k, v)
    for g, r, x in zip(got, want, (q, k, v)):
        assert g.shape == x.shape and g.dtype == jnp.bfloat16
        err = jnp.linalg.norm((g - r).astype(jnp.float32))
        assert float(err / jnp.linalg.norm(r.astype(jnp.float32))) < 0.02
