"""The layout in which the flash kernels' operands cross HBM (PR 38): q, k,
v, do and out, dq, dk, dv as [b, s, heads x d], `lcm(d, 128) / d` heads a
program (two of 64 a lane block).  CPU interpret mode, at the smallest
shapes the tiling allows; a file of its own so that `--dist loadfile` can
give it a worker."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn
from ray_tpu.ops import dispatch
from attention_cases import (  # noqa: F401 (the fixture is autouse)
    _interpret_mode, _pallas_calls)


def _operands(b, sq, sk, h, d, e, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (b, sq, h, d), dtype),
            jax.random.normal(keys[1], (b, sk, h, d), dtype),
            jax.random.normal(keys[2], (b, sk, h, e), dtype),
            jax.random.normal(keys[3], (b, sq, h, e), jnp.float32))


def _tables(b, sk, d):
    """(cos, sin) float32 [b, sk, d/2]: every row of the batch its own
    positions."""
    inv = 1.0 / 10000.0 ** (np.arange(d // 2) / (d // 2))
    pos = np.arange(sk)[None, :] + 7 * np.arange(b)[:, None]
    angle = pos[..., None] * inv
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def _reference(q, k, v, rope, window):
    sq, sk = q.shape[1], k.shape[1]
    if rope is not None:
        cos, sin = rope
        q = attn.rope_reference(q, cos[:, sk - sq:], sin[:, sk - sq:])
        k = attn.rope_reference(k, cos, sin)
    return attn.attention_reference(q, k, v, True, None, window)


def _value_and_grads(fn, q, k, v, w):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(),
        argnums=(0, 1, 2)))(q, k, v)


# (name, b, sq, sk, heads, d, e, rope, window, the plan's word)
CASES = [
    ("pair of 64, causal", 2, 256, 256, 2, 64, 64, False, None, "2x64"),
    ("pair of 64, rope", 1, 256, 256, 4, 64, 64, True, None, "2x64"),
    ("pair of 64, fewer queries than keys", 1, 128, 256, 2, 64, 64, True,
     None, "2x64"),
    ("one of 128, window and rope", 1, 512, 512, 2, 128, 128, True, 128,
     "1x128"),
    ("values narrower than keys", 1, 256, 256, 2, 128, 64, False, None,
     "2x128"),
    ("values wider than keys", 1, 256, 256, 2, 64, 128, False, None,
     "2x64"),
    ("four of 32", 1, 256, 256, 4, 32, 32, True, None, "4x32"),
    ("three heads of 64, padded by a zero head", 1, 256, 256, 3, 64, 64,
     True, None, "2x64"),
]


@pytest.mark.parametrize("name,b,sq,sk,h,d,e,rope,window,word", CASES,
                         ids=[c[0] for c in CASES])
def test_values_and_all_three_gradients_match_the_reference(
        name, b, sq, sk, h, d, e, rope, window, word):
    q, k, v, w = _operands(b, sq, sk, h, d, e)
    tables = _tables(b, sk, d) if rope else None
    before = dict(dispatch.taken().get("flash_attention.plan", {}))
    got = _value_and_grads(
        lambda q, k, v: attn.flash_attention(
            q, k, v, block_q=128, block_k=128, window=window, rope=tables),
        q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: _reference(q, k, v, tables, window), q, k, v, w)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, r in zip(got[1], want[1]):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)
    plans = [p for p, n in dispatch.taken()["flash_attention.plan"].items()
             if n > before.get(p, 0)]
    assert plans and all(
        p.endswith(f",operands_bshd,heads{word}") for p in plans), plans


@pytest.mark.parametrize("d,delta", [(64, 0), (64, 128), (32, -64)])
def test_chunk_with_a_gradient_on_lse_matches_the_reference(d, delta):
    """`flash_attention_chunk` as ring attention calls it: offsets off the
    diagonal, and a cotangent on the lse OUTPUT, which enters the backward
    as -dlse beside the delta the kernel makes from do and out."""
    b, s, h = 1, 256, 2
    q, k, v, w = _operands(b, s, s, h, d, d, seed=3)
    wl = jax.random.normal(jax.random.PRNGKey(9), (b * h, s), jnp.float32)

    def flash(q, k, v):
        out, lse = attn.flash_attention_chunk(q, k, v, delta, 0)
        return (out * w).sum() + (jnp.where(lse > -1e20, lse, 0.0) * wl).sum()

    def reference(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        seen = (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None] + delta)
        logits = jnp.where(seen[None, None], logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        rows = seen.any(axis=1)
        probs = jnp.where(rows[None, None, :, None],
                          jax.nn.softmax(logits, axis=-1), 0.0)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        lse = jnp.where(rows[None, None], lse, 0.0).reshape(b * h, s)
        return (out * w).sum() + (lse * wl).sum()

    got = jax.jit(jax.value_and_grad(flash, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(reference, argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("rope", [False, True])
@pytest.mark.parametrize("changed", [0, 1])
def test_the_two_heads_of_a_pair_do_not_leak_into_each_other(changed, rope):
    """One head's q, k, v and do perturbed: the other head's out, dq, dk
    and dv are bit for bit what they were, in bfloat16 as the cells run it
    (a mask a lane off, or a sum joined from the wrong head's lanes, moves
    them)."""
    b, s, h, d = 1, 256, 2, 64
    q, k, v, w = _operands(b, s, s, h, d, d, seed=5, dtype=jnp.bfloat16)
    tables = _tables(b, s, d) if rope else None
    other = 1 - changed

    def run(q, k, v, w):
        out, grads = jax.vjp(lambda q, k, v: attn.flash_attention(
            q, k, v, block_q=128, block_k=128, rope=tables), q, k, v)
        return (out, *grads(w.astype(out.dtype)))

    def perturbed(x, seed):
        noise = jax.random.normal(jax.random.PRNGKey(seed), x.shape, x.dtype)
        return x.at[:, :, changed].set(x[:, :, changed] + noise[:, :, changed])

    base = run(q, k, v, w)
    moved = run(perturbed(q, 11), perturbed(k, 12), perturbed(v, 13),
                perturbed(w, 14))
    for was, now in zip(base, moved):
        assert (np.asarray(was[:, :, other]) == np.asarray(
            now[:, :, other])).all()
        assert (np.asarray(was[:, :, changed]) != np.asarray(
            now[:, :, changed])).any()


@pytest.mark.parametrize("h,d,e,heads", [(4, 64, 64, 2), (2, 128, 128, 1),
                                         (8, 32, 32, 4), (3, 64, 64, 2)])
def test_operands_and_results_cross_as_the_projections_lay_them(h, d, e,
                                                                heads):
    """Both calls take q, k, v (do, out) and give out (dq, dk, dv) as [b, s,
    heads x d]: no operand with a last axis under 128, no [b x h, s, d];
    lse and -dlse stay [b x h, 8, s]; the grid's first axis is the row
    times the GROUPS of `heads` heads, and program g reads lane block g %
    groups of row g // groups; the tables, widened to the group's lanes,
    come last."""
    b, s = 2, 256
    hp = -(-h // heads) * heads         # with the zero heads behind it
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    xv = jax.ShapeDtypeStruct((b, s, h, e), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((b, s, d // 2), jnp.float32)

    def loss(q, k, v, cos, sin):
        return attn.flash_attention(
            q, k, v, block_q=128, block_k=128,
            rope=(cos, sin)).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, xv, t, t)
    fwd, bwd = sorted(_pallas_calls(jaxpr.jaxpr),
                      key=lambda c: len(c.outvars))
    wide, wide_v = (b, s, hp * d), (b, s, hp * e)
    rows, table = (b * hp, 8, s), (b, s, heads * d)
    assert [v.aval.shape for v in fwd.invars] == [
        (2,), wide, wide, wide_v, table, table]
    assert [v.aval.shape for v in fwd.outvars] == [wide_v, rows]
    assert [v.aval.shape for v in bwd.invars] == [
        (2,), wide, wide, wide_v, wide_v, rows, rows, wide_v, table, table]
    assert [v.aval.shape for v in bwd.outvars] == [wide, wide, wide_v]
    groups = hp // heads
    offs = jnp.zeros((2,), jnp.int32)
    for call, blocks in ((fwd, {0: (1, 128, heads * d), 1: (1, s, heads * d),
                                2: (1, s, heads * e)}),
                         (bwd, {0: (1, s, heads * d), 1: (1, 128, heads * d),
                                3: (1, s, heads * e),
                                4: (heads, 8, s)})):
        mapping = call.params["grid_mapping"]
        assert mapping.grid == (b * groups, 2)
        for operand, shape in blocks.items():
            block = mapping.block_mappings[operand]
            assert tuple(getattr(x, "block_size", x)
                         for x in block.block_shape) == shape, block
            index = block.index_map_jaxpr
            for g in range(b * groups):
                at = [int(x) for x in jax.core.eval_jaxpr(
                    index.jaxpr, index.consts, jnp.int32(g), jnp.int32(1),
                    offs)]
                if len(shape) == 3 and shape[0] == heads and shape[1] == 8:
                    assert at == [g, 0, 0]
                else:
                    assert at[0] == g // groups and at[2] == g % groups
