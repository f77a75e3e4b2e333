"""Flash attention kernel vs reference (CPU interpret mode).

Mirrors the reference's kernel-test strategy (colocated unit tests with
ground-truth comparisons, SURVEY.md §4 tier a)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


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

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
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


def _rand_qkv(seed, b, s, h, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32)
                 for k in ks)


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

    g1 = jax.grad(loss_merged, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)


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

def _grads_and_value(fn, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) * w)

    return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


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
    g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
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


# ---------------------------------------------------------------------------
# Sliding window: query t sees keys s with 0 <= t - s < window
# ---------------------------------------------------------------------------

def _masked_reference(sq, sk, d, causal, window):
    """Attention under the explicit mask, end-aligned (written out here)."""
    behind = (jnp.arange(sq)[:, None] + (sk - sq)) - jnp.arange(sk)[None, :]
    seen = jnp.ones((sq, sk), bool)
    if causal:
        seen = behind >= 0
    if window is not None:
        seen = seen & (behind < window)

    def masked(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    return masked


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
# dq out of the backward's one pass: stored where a head has one key tile,
# summed in float32 over the key-tile axis where it has several
# ---------------------------------------------------------------------------

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
    g_ref = jax.grad(loss(lambda q, k, v: ring._chunk_attention(
        q, k, v, mask, d ** -0.5)), argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)
    if delta == -300:
        assert not any(np.asarray(a).any() for a in g)


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


def _pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for val in eqn.params.values():
            inner = getattr(val, "jaxpr", val)      # a ClosedJaxpr's own
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner)
    return found


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


# ---------------------------------------------------------------------------
# Rope inside the kernels (rope=): q and k as projected, roped where the
# tiles are loaded; against rope in XLA before the same kernels
# ---------------------------------------------------------------------------

def _rope_tables(b, sk, d, starts=(3, 500)):
    """(cos, sin) [b, sk, d/2] float32 at positions that differ by row and
    do not start at 0, each value cut to the eight bits a bfloat16 holds:
    a bfloat16 operand times such a value is exact in float32, so x * cos
    + y * sin is rounded once whether or not the CPU's compiler fuses the
    multiply into the add (it does in one program and not in the other,
    which moves one rounding in 2 ** 16 of bfloat16 values; the TPU's vector
    unit has no such fused form to choose).  The bit-for-bit tests below
    test the kernels, not the host's code generator."""
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = (jnp.asarray(starts[:b], jnp.float32)[:, None]
           + jnp.arange(sk, dtype=jnp.float32)[None, :])
    freqs = pos[:, :, None] * inv
    return tuple(t.astype(jnp.bfloat16).astype(jnp.float32)
                 for t in (jnp.cos(freqs), jnp.sin(freqs)))


def _rope_outside(rope, sq):
    """q, k -> rope in XLA, the queries on the tables' last sq rows."""
    cos, sin = rope
    sk = cos.shape[1]
    return (lambda q: attn.rope_reference(q, cos[:, sk - sq:],
                                          sin[:, sk - sq:]),
            lambda k: attn.rope_reference(k, cos, sin))


# id: (sq, sk, d, block_q, block_k, window, causal)
_ROPES = {
    "d64": (256, 256, 64, 128, 128, None, True),
    "d128": (256, 256, 128, 128, 128, None, True),
    "fewer_queries_than_keys": (128, 384, 64, 128, 128, None, True),
    "fewer_queries_d128_two_query_tiles": (256, 512, 128, 128, 256, None,
                                           True),
    "several_key_tiles_narrow_forward": (512, 512, 64, 256, 128, None, True),
    "narrow_backward": (512, 512, 64, 128, 512, None, True),
    "window": (512, 512, 64, 128, 128, 200, True),
    "window_fewer_queries": (256, 768, 128, 128, 128, 200, True),
    "default_blocks": (1024, 1024, 64, None, None, None, True),
    "not_causal": (256, 512, 64, 128, 128, None, False),
}


def _rope_case(name, dtype):
    sq, sk, d, bq, bk, window, causal = _ROPES[name]
    b, h = 2, 2
    ks = jax.random.split(jax.random.PRNGKey(len(name) + d), 4)
    q, w = (jax.random.normal(x, (b, sq, h, d), jnp.float32).astype(dtype)
            for x in ks[:2])
    k, v = (jax.random.normal(x, (b, sk, h, d), jnp.float32).astype(dtype)
            for x in ks[2:])
    kw = dict(causal=causal, block_q=bq, block_k=bk, window=window)
    return q, k, v, w, _rope_tables(b, sk, d), kw


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("name", sorted(_ROPES))
def test_rope_in_kernel_forward_is_bit_for_bit_rope_in_xla(name, dtype):
    """out AND lse: the kernels rope in float32 and round to the operand's
    dtype before the scale and the first matmul, which is where rope in
    XLA rounds."""
    q, k, v, _, rope, kw = _rope_case(name, dtype)
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1]
    window = kw["window"]
    blocks = ((kw["block_q"], kw["block_k"]),) * 2 if kw["block_q"] \
        else attn.default_blocks(d, sq, sk, dtype, window)
    rope_q, rope_k = _rope_outside(rope, sq)

    def chunk(q, k, rope):
        return attn._chunk(q, k, v, sk - sq, 0, kw["causal"], d ** -0.5,
                           blocks, window, rope)

    out, lse = chunk(q, k, rope)
    out_x, lse_x = chunk(rope_q(q), rope_k(k), None)
    assert out.dtype == dtype
    if dtype == jnp.float32:
        # float32 operands: the products are not exact, and the host's
        # fused multiply-adds move the last bit (see _rope_tables)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_x),
                                   atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_x),
                                   atol=2e-6, rtol=2e-6)
    else:
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(out_x, np.float32))
        np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_x))
    # and the public call gives that out
    np.testing.assert_array_equal(
        np.asarray(attn.flash_attention(q, k, v, rope=rope, **kw),
                   np.float32), np.asarray(out, np.float32))


@pytest.mark.parametrize("name", sorted(_ROPES))
def test_rope_in_kernel_gradients_lose_a_rounding_not_gain_one(name):
    """bfloat16 operands, gradients with respect to the UN-roped q and k.
    Rope in XLA rounds the kernel's dq and dk to bfloat16, turns them back
    through rope in float32 and rounds again; the kernel turns its float32
    sums and rounds once.  So against the float32 reference's gradients
    the kernel's are no further off than today's, and the two differ by a
    bfloat16 rounding of the largest value at most.  dv does not meet
    rope: bit for bit."""
    q, k, v, w, rope, kw = _rope_case(name, jnp.bfloat16)
    sq, d = q.shape[1], q.shape[-1]
    rope_q, rope_k = _rope_outside(rope, sq)
    w32 = w.astype(jnp.float32)

    def grads(fn, *xs):
        return jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w32), argnums=(0, 1, 2))(*xs)

    inside = grads(lambda q, k, v: attn.flash_attention(
        q, k, v, rope=rope, **kw), q, k, v)
    outside = grads(lambda q, k, v: attn.flash_attention(
        rope_q(q), rope_k(k), v, **kw), q, k, v)
    masked = _masked_reference(sq, k.shape[1], d, kw["causal"], kw["window"])
    exact = grads(lambda q, k, v: masked(rope_q(q), rope_k(k), v),
                  *(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_array_equal(np.asarray(inside[2], np.float32),
                                  np.asarray(outside[2], np.float32))
    for got, today, ref in zip(inside[:2], outside[:2], exact[:2]):
        assert got.dtype == jnp.bfloat16
        got, today, ref = (np.asarray(x, np.float32)
                           for x in (got, today, ref))
        top = np.abs(ref).max()
        assert np.abs(got - today).max() <= 2.0 ** -7 * top
        assert np.abs(got - ref).max() <= 2.0 ** -5 * top

        def rms(x):
            return float(np.sqrt(np.mean(x * x)))

        assert rms(got - ref) <= 1.01 * rms(today - ref), (
            rms(got - ref), rms(today - ref))


@pytest.mark.parametrize("name", ["d64", "d128", "fewer_queries_than_keys",
                                  "window"])
def test_rope_in_kernel_float32_gradients_match_the_reference(name):
    q, k, v, w, rope, kw = _rope_case(name, jnp.float32)
    sq, d = q.shape[1], q.shape[-1]
    rope_q, rope_k = _rope_outside(rope, sq)
    masked = _masked_reference(sq, k.shape[1], d, kw["causal"], kw["window"])
    out, g = _grads_and_value(lambda q, k, v: attn.flash_attention(
        q, k, v, rope=rope, **kw), q, k, v, w)
    ref, g_ref = _grads_and_value(
        lambda q, k, v: masked(rope_q(q), rope_k(k), v), q, k, v, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)


def _new_plans(before):
    from ray_tpu.ops import dispatch

    after = dispatch.taken()
    return {op: {p: n - before.get(op, {}).get(p, 0)
                 for p, n in after.get(op, {}).items()
                 if n - before.get(op, {}).get(p, 0)}
            for op in ("flash_attention", "flash_attention.plan")}


@pytest.mark.parametrize("shape,blocks,path", [
    ((1, 100, 2, 32), {"block_k": 64}, "xla"),  # a block that does not
    ((1, 256, 2, 64), {"block_q": 96}, "xla"),  # divide the sequence
    ((1, 132, 2, 64), {}, "interpret")])        # queries begin at row 4
def test_rope_outside_the_kernels_where_they_cannot_take_it(shape, blocks,
                                                            path):
    """The XLA fallback ropes with rope_reference and goes on as without;
    so does a kernel call whose queries begin at a row of the tables that
    is no multiple of 8 (128 queries against 132 keys).  Neither plan says
    rope_in_kernel."""
    from ray_tpu.ops import dispatch

    b, sk, h, d = shape
    sq = 128 if sk == 132 else sk
    ks = jax.random.split(jax.random.PRNGKey(sk), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k, v = (jax.random.normal(x, shape, jnp.float32) for x in ks[1:])
    rope = _rope_tables(b, sk, d)
    rope_q, rope_k = _rope_outside(rope, sq)
    before = dispatch.taken()
    out = attn.flash_attention(q, k, v, rope=rope, **blocks)
    new = _new_plans(before)
    assert new["flash_attention"] == {path: 1}
    assert not any("rope_in_kernel" in p for p in new["flash_attention.plan"])
    ref = attn.attention_reference(rope_q(q), rope_k(k), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("roped", [False, True])
def test_rope_is_seen_in_the_input_plan_and_operands(roped):
    """rope=None builds exactly the kernels without: four and seven
    operands (the backward's seven and the output, from which it makes
    delta), three scratch buffers in the backward, one in the forward (the
    pair's values, turned), a plan without the token.  rope=(cos, sin): the two float32 tables, [b,
    sk, 2 x d] for the two heads of 64 a program works, come LAST (a
    trace's face of the call, result and first operand, does not move),
    their block index is the row's for every pair of heads and tile of it,
    one scratch more in each kernel, and the plan says rope_in_kernel."""
    from ray_tpu.ops import dispatch

    b, s, h, d = 2, 512, 4, 64
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((b, s, d // 2), jnp.float32)

    def loss(q, k, v, cos, sin):
        return attn.flash_attention(
            q, k, v, block_q=128, block_k=256,
            rope=(cos, sin) if roped else None).astype(jnp.float32).sum()

    before = dispatch.taken()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x, t, t)
    plans = _new_plans(before)["flash_attention.plan"]
    token = ",rope_in_kernel" if roped else ""
    assert list(plans) == [
        "fwd128x256,bwd128x256,dq_in_pass,dq_over2tiles,scale_folded,"
        "dead33/20%" + token + ",operands_bshd,heads2x64"]
    fwd, bwd = sorted(_pallas_calls(jaxpr.jaxpr),
                      key=lambda c: len(c.outvars))
    extra = 2 if roped else 0
    assert len(fwd.invars) == 4 + extra and len(bwd.invars) == 8 + extra
    for call, scratch in ((fwd, 1), (bwd, 3)):
        mapping = call.params["grid_mapping"]
        assert mapping.num_scratch_operands == scratch + (1 if roped else 0)
        assert call.invars[0].aval.shape == (2,)            # offs first
        assert call.invars[1].aval.shape == (b, s, h * d)   # then q
        if not roped:
            continue
        for table, block in zip(call.invars[-2:],
                                mapping.block_mappings[-2 - len(
                                    call.outvars):][:2]):
            assert table.aval.shape == (b, s, 2 * d)
            assert table.aval.dtype == jnp.float32
            index = block.index_map_jaxpr
            offs = jnp.zeros((2,), jnp.int32)
            for g in range(b * h // 2):
                for i in range(2):
                    at = jax.core.eval_jaxpr(index.jaxpr, index.consts,
                                             jnp.int32(g), jnp.int32(i),
                                             offs)
                    assert [int(x) for x in at] == [g // (h // 2), 0, 0]


def test_rope_under_a_batch_sharded_mesh_is_the_one_device_call():
    """flash_attention's shard_map hands each shard its rows of the tables
    with its rows of q, k and v (the fsdp cell's path)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    b, s, h, d = 4, 256, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(x, (b, s, h, d), jnp.float32) for x in ks)
    rope = _rope_tables(b, s, d, starts=(3, 500, 40, 77))

    def call(q, k, v, cos, sin):
        return attn.flash_attention(q, k, v, rope=(cos, sin), block_q=128,
                                    block_k=128)

    one = call(q, k, v, *rope)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("fsdp",))
    rows = NamedSharding(mesh, P("fsdp"))
    with jax.sharding.set_mesh(mesh):
        four = jax.jit(call)(*(jax.device_put(x, rows)
                               for x in (q, k, v, *rope)))
    np.testing.assert_array_equal(np.asarray(four), np.asarray(one))


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

    got = jax.grad(loss(lambda q, k, v: attn.flash_attention(
        q, k, v, block_q=128, block_k=128)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attn.attention_reference), argnums=(0, 1, 2))(
        q, k, v)
    for g, r, x in zip(got, want, (q, k, v)):
        assert g.shape == x.shape and g.dtype == jnp.bfloat16
        err = jnp.linalg.norm((g - r).astype(jnp.float32))
        assert float(err / jnp.linalg.norm(r.astype(jnp.float32))) < 0.02


# ---------------------------------------------------------------------------
# A window WITH rope at head size 128, and a rope over half the head
# (models/swa_moe.py's two calls: the sliding layers', the full layers')
# ---------------------------------------------------------------------------

def _half_rope_tables(b, sk, d):
    """A partial rope's tables as a model hands them to the kernels: the
    first d/4 pairs turn (factor 1.5 in cos and sin, as yarn's attention
    factor sits there), the other d/4 pass through on cos 1 and sin 0."""
    inv = 1.0 / (5e5 ** (jnp.arange(0, d // 2, 2, dtype=jnp.float32)
                         / (d // 2)))
    angle = (jnp.arange(sk, dtype=jnp.float32)[None, :, None] + 7.0) * inv
    angle = jnp.broadcast_to(angle, (b, sk, d // 4))
    tail = jnp.ones((b, sk, d // 4), jnp.float32)
    return (jnp.concatenate([1.5 * jnp.cos(angle), tail], axis=-1),
            jnp.concatenate([1.5 * jnp.sin(angle), 0.0 * tail], axis=-1))


def _half_roped(x, cos, sin):
    """The published partial rope, written out: the first d/2 columns
    turned, pair (i, i + d/4), the others as they are.  cos, sin [b, s,
    d/4]."""
    d = x.shape[-1]
    a, b_, rest = x[..., :d // 4], x[..., d // 4:d // 2], x[..., d // 2:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([a * c - b_ * s, b_ * c + a * s, rest], axis=-1)


def _rotary_halves_first(x):
    """[rot_a | rot_b | pass_a | pass_b] -> [rot_a | pass_a | rot_b |
    pass_b]: the one reordering of q's and k's columns under which the
    kernels' whole-head pairing (i, i + d/2) is the partial rope's."""
    d = x.shape[-1]
    return x.reshape(*x.shape[:-1], 2, 2, d // 4).swapaxes(-2, -3).reshape(
        x.shape)


# id: (sq, sk, window, block); None: `default_blocks`' plan
_WINDOWED_ROPES = {
    "window_under_a_block": (512, 512, 100, 128),
    "window_is_a_block": (512, 512, 128, 128),
    "window_over_the_sequence": (256, 256, 1024, 128),
    "default_blocks_window_is_a_block": (1024, 1024, 512, None),
    "window_fewer_queries_than_keys": (256, 512, 128, 128),
    "half_rope_full": (256, 256, None, 128),
    "half_rope_default_blocks": (1024, 1024, None, None),
}


@pytest.mark.parametrize("name", sorted(_WINDOWED_ROPES))
def test_window_with_rope_at_head_128_matches_reference(name):
    """flash_attention(window=, rope=) at head size 128, kernels
    interpreted, float32: the output and dq, dk, dv with respect to the
    UN-roped operands against `attention_reference` on operands roped in
    XLA.  The half-rope cases hand the kernels reordered columns and
    tables with an identity tail, and are held to the published partial
    rope on the columns as published."""
    sq, sk, window, block = _WINDOWED_ROPES[name]
    b, h, d = 2, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    q, w = (jax.random.normal(x, (b, sq, h, d), jnp.float32) for x in ks[:2])
    k, v = (jax.random.normal(x, (b, sk, h, d), jnp.float32) for x in ks[2:])
    half = name.startswith("half_rope")
    if half:
        rope = _half_rope_tables(b, sk, d)
        turning = tuple(t[..., :d // 4] for t in rope)

        def rope_q(x):
            return _half_roped(x, *(t[:, sk - sq:] for t in turning))

        def rope_k(x):
            return _half_roped(x, *turning)

        to_kernel = _rotary_halves_first
    else:
        rope = _rope_tables(b, sk, d)
        rope_q, rope_k = _rope_outside(rope, sq)

        def to_kernel(x):
            return x

    out, g = _grads_and_value(lambda q, k, v: attn.flash_attention(
        to_kernel(q), to_kernel(k), v, rope=rope, window=window,
        block_q=block, block_k=block), q, k, v, w)
    ref, g_ref = _grads_and_value(
        lambda q, k, v: attn.attention_reference(
            rope_q(q), rope_k(k), v, window=window), q, k, v, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)
    for got, want, what in zip(g, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4, err_msg=what)
    plan = list(attn.dispatch.taken()["flash_attention.plan"])
    assert any(p.endswith("rope_in_kernel,operands_bshd,heads1x128")
               and ("window" in p) == (window is not None and window < sk)
               for p in plan), plan


def test_long_roped_forward_asks_more_vmem_and_the_others_what_they_did():
    """The forward's VMEM ask follows what the call can see: 32 MiB for
    every call without rope and for a roped one whose tables are short (the
    dense cells' 2048 x 64), more where the two float32 tables of a long
    row would not fit beside k and v (8192 x 128: 40)."""
    import re

    def ask(sk, d, roped):
        x = jax.ShapeDtypeStruct((1, sk, 2, d), jnp.bfloat16)
        rope = tuple(jax.ShapeDtypeStruct((1, sk, d // 2), jnp.float32)
                     for _ in range(2)) if roped else None
        text = str(jax.make_jaxpr(lambda q, k, v, rope: attn.flash_attention(
            q, k, v, rope=rope))(x, x, x, rope))
        return sorted({int(m) >> 20 for m in
                       re.findall(r"vmem_limit_bytes=(\d+)", text)})

    assert ask(2048, 64, True) == ask(2048, 64, False) == [32]
    assert ask(8192, 128, False) == [32]
    assert ask(8192, 128, True) == [40]


# ---------------------------------------------------------------------------
# A head of 256 (two lane blocks, one head a program) with a rotary QUARTER:
# the tables hold cos 1 and sin 0 for the pairs that pass through, so the
# kernels' whole-head turn is the quarter turn (models/gdn_moe.py)
# ---------------------------------------------------------------------------

def _quarter_tables(b, s, d, theta=1e7):
    r = d // 4
    inv_freq = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    passing = (d - r) // 2
    cos = jnp.concatenate([jnp.cos(angle), jnp.ones((s, passing))], axis=1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.zeros((s, passing))], axis=1)
    return tuple(jnp.broadcast_to(t, (b, s, d // 2)) for t in (cos, sin))


def test_head_256_with_a_rotary_quarter_values_and_grads():
    b, s, h, d = 1, 256, 2, 256
    q, k, v = _rand_qkv(7, b, s, h, d)
    w = jax.random.normal(jax.random.PRNGKey(8), (b, s, h, d))
    rope = _quarter_tables(b, s, d)
    kw = dict(causal=True, sm_scale=1.0 / 16, block_q=128, block_k=128)

    def roped_reference(q, k, v):
        return attn.attention_reference(
            attn.rope_reference(q, *rope), attn.rope_reference(k, *rope), v,
            causal=True, sm_scale=1.0 / 16)

    (out, grads), (out_ref, grads_ref) = (
        _grads_and_value(f, q, k, v, w) for f in (
            lambda q, k, v: attn.flash_attention(q, k, v, rope=rope, **kw),
            roped_reference))
    for name, a, e in zip(("out", "dq", "dk", "dv"), (out, *grads),
                          (out_ref, *grads_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=3e-5,
                                   rtol=3e-5, err_msg=name)
    assert bool((rope[0][0, :, d // 8:] == 1).all())
    plans = attn.dispatch.taken()["flash_attention.plan"]
    assert any(p.endswith("rope_in_kernel,operands_bshd,heads1x256")
               for p in plans), plans
