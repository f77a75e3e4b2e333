"""Latent attention's parts through the flash kernels (PR 35) vs reference
(CPU interpret mode): `latent_flash_attention`, which PR 38's layout of the
whole-operand calls leaves as it was.  Moved here from test_ops_attention.py
case for case (PR 38), so that `--dist loadfile` gives these, the slowest
group of that file, a worker of their own."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn
from attention_cases import (  # noqa: F401 (the fixture is autouse)
    _grads_and_value, _interpret_mode, _new_plans, _pallas_calls,
    _rope_tables)


# ---------------------------------------------------------------------------
# Latent attention in PARTS (PR 35): q un-roped, a head's [k_nope | v] as its
# projection lays it, one rotary key for all heads; the kernels rope and put
# the keys together in VMEM.  The oracle is `attention_reference` on q, k, v
# put together in XLA, as models/latent_moe.py did before.
# ---------------------------------------------------------------------------

def _latent_operands(b, sq, sk, h, nope, r, e, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + sq + sk + nope), 4)
    q = jax.random.normal(ks[0], (b, sq, h, nope + r), dtype)
    kv = jax.random.normal(ks[1], (b, sk, h, nope + e), dtype)
    k_pe = jax.random.normal(ks[2], (b, sk, r), dtype)
    w = jax.random.normal(ks[3], (b, sq, h, e), jnp.float32)
    return q, kv, k_pe, w


def _latent_whole(q, kv, k_pe, rope, nope):
    """q' = [q_nope | rope(q_pe)], k' = [k_nope | rope(k_pe) for every
    head] and v, put together in XLA."""
    cos, sin = rope
    b, sq, h, _ = q.shape
    sk, r = k_pe.shape[1], k_pe.shape[-1]
    q = jnp.concatenate([q[..., :nope], attn.rope_reference(
        q[..., nope:], cos[:, sk - sq:], sin[:, sk - sq:])], axis=-1)
    pe = attn.rope_reference(k_pe[:, :, None, :], cos, sin)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(pe, (b, sk, h, r))], axis=-1)
    return q, k, kv[..., nope:]


def _latent_assembled(rope, nope, causal=True):
    """(q, kv, k_pe) -> attention_reference over the whole operands."""
    return lambda q, kv, k_pe: attn.attention_reference(
        *_latent_whole(q, kv, k_pe, rope, nope), causal=causal)


# (nope, r, e, sq, sk, block_q, block_k, causal)
_LATENT = [
    (128, 64, 128, 256, 256, 128, 128, True),     # the cell's widths
    (128, 64, 128, 512, 512, None, None, True),   # default_blocks' plan
    (128, 64, 128, 512, 512, 256, 128, True),     # narrow forward steps
    (128, 64, 128, 512, 512, 128, 256, True),     # narrow backward steps
    (128, 64, 128, 128, 384, 128, 128, True),     # fewer queries than keys
    (128, 64, 128, 256, 256, 128, 128, False),
    (32, 16, 32, 128, 128, None, None, True),     # the tiny model's widths
]


@pytest.mark.parametrize("nope,r,e,sq,sk,bq,bk,causal", _LATENT)
def test_latent_parts_values_and_grads_match_reference(
        nope, r, e, sq, sk, bq, bk, causal):
    """out and the gradients with respect to q, kv and k_pe (whose is the
    sum over the heads) of the parts entry against the reference on operands
    put together in XLA; dkv comes out laid as kv, [dk_nope | dv]."""
    b, h = 2, 2
    q, kv, k_pe, w = _latent_operands(b, sq, sk, h, nope, r, e)
    rope = _rope_tables(b, sk, r)
    before = attn.dispatch.taken()
    out, grads = _grads_and_value(
        lambda q, kv, k_pe: attn.latent_flash_attention(
            q, kv, k_pe, rope, causal=causal, block_q=bq, block_k=bk),
        q, kv, k_pe, w)
    ref, ref_grads = _grads_and_value(_latent_assembled(rope, nope, causal),
                                      q, kv, k_pe, w)
    assert out.shape == (b, sq, h, e)
    assert [g.shape for g in grads] == [q.shape, kv.shape, k_pe.shape]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for g, want in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   atol=5e-4, rtol=5e-4)
    new = _new_plans(before)
    assert set(new["flash_attention"]) == {"interpret"}
    (plan,) = new["flash_attention.plan"]
    assert plan.endswith(f",dqk{nope + r},dv{e},latent_parts,"
                         f"rope_in_kernel{r}of{nope + r}")


def test_latent_parts_bfloat16_is_rope_in_xla_before_the_whole_kernels():
    """bfloat16 operands: the parts call's output is that of the
    whole-operand 192 / 128 kernels on q, k, v roped and put together in
    XLA (rope is rounded where XLA rounds it, the scores contract over the
    same 192 columns), but for the order of the float32 sum in p . v, which
    two heads a program take against their values turned (PR 38): under one
    element in a thousand, one bfloat16 rounding apart.  Its gradients are
    within a bfloat16 rounding of theirs, with one rounding fewer (rope's
    transpose on the float32 sums)."""
    b, h, s, nope, r, e = 2, 2, 256, 128, 64, 128
    q, kv, k_pe, w = _latent_operands(b, s, s, h, nope, r, e, jnp.bfloat16)
    rope = _rope_tables(b, s, r)

    def whole(q, kv, k_pe):
        return attn.flash_attention(*_latent_whole(q, kv, k_pe, rope, nope),
                                    block_q=128, block_k=128)

    def parts(q, kv, k_pe):
        return attn.latent_flash_attention(q, kv, k_pe, rope, block_q=128,
                                           block_k=128)

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * w).sum()

    out, out_whole = (np.asarray(fn(q, kv, k_pe), np.float32)
                      for fn in (parts, whole))
    assert (out != out_whole).mean() < 1e-3
    np.testing.assert_allclose(out, out_whole, rtol=2.0 ** -7, atol=0)
    got = jax.jit(jax.grad(loss(parts), argnums=(0, 1, 2)))(q, kv, k_pe)
    want = jax.jit(jax.grad(loss(whole), argnums=(0, 1, 2)))(q, kv, k_pe)
    exact = jax.jit(jax.grad(loss(_latent_assembled(rope, nope)),
                             argnums=(0, 1, 2)))(
        *(x.astype(jnp.float32) for x in (q, kv, k_pe)))
    for g, x, f, arg in zip(got, want, exact, (q, kv, k_pe)):
        assert g.shape == arg.shape and g.dtype == jnp.bfloat16
        g, x = g.astype(jnp.float32), x.astype(jnp.float32)
        norm = float(jnp.linalg.norm(f))
        assert float(jnp.linalg.norm(g - x)) < 0.01 * norm
        # no further from the float32 gradient than the XLA-roped path
        assert float(jnp.linalg.norm(g - f)) <= 1.02 * float(
            jnp.linalg.norm(x - f))


def test_latent_parts_gradient_through_the_lse_output():
    """The parts' backward makes delta itself and adds the -dlse it is
    handed: a loss that reads lse as well (ring attention's merge does)
    gives the gradients of the same loss through whole operands roped and
    put together in XLA."""
    b, h, s, nope, r, e = 1, 2, 256, 128, 64, 128
    q, kv, k_pe, w = _latent_operands(b, s, s, h, nope, r, e)
    rope = _rope_tables(b, s, r)
    blocks = ((128, 128),) * 2
    scale = (nope + r) ** -0.5

    def loss(out, lse):
        return (out * w).sum() + (jnp.sin(lse) * 3.0).sum()

    def parts(q, kv, k_pe):
        return loss(*attn._chunk(q, kv, k_pe, 0, 0, True, scale, blocks,
                                 None, rope))

    def whole(q, kv, k_pe):
        return loss(*attn._chunk(*_latent_whole(q, kv, k_pe, rope, nope),
                                 0, 0, True, scale, blocks))

    got = jax.jit(jax.grad(parts, argnums=(0, 1, 2)))(q, kv, k_pe)
    want = jax.jit(jax.grad(whole, argnums=(0, 1, 2)))(q, kv, k_pe)
    for g, x in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x),
                                   atol=5e-4, rtol=5e-4)


def test_latent_parts_under_a_mesh_are_the_one_device_call():
    """Batch over fsdp and heads over tensor: a shard gets its rows of
    everything, its heads of q and kv (the last axis of the projection's
    output by whole heads), and the rotary key and the tables whole over
    tensor."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    b, h, s, nope, r, e = 2, 4, 256, 128, 64, 128
    q, kv, k_pe, w = _latent_operands(b, s, s, h, nope, r, e)
    rope = _rope_tables(b, s, r)

    def call(q, kv, k_pe, cos, sin):
        return attn.latent_flash_attention(q, kv, k_pe, (cos, sin),
                                           block_q=128, block_k=128)

    def grads(*a):
        return jax.grad(lambda *a: (call(*a) * w).sum(), argnums=(0, 1, 2))(
            *a)

    one, one_grads = call(q, kv, k_pe, *rope), grads(q, kv, k_pe, *rope)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("fsdp", "tensor"))
    by_head = NamedSharding(mesh, P("fsdp", None, "tensor"))
    by_row = NamedSharding(mesh, P("fsdp"))
    with jax.sharding.set_mesh(mesh):
        placed = [jax.device_put(x, sh) for x, sh in zip(
            (q, kv, k_pe, *rope), (by_head, by_head, by_row, by_row, by_row))]
        four, four_grads = jax.jit(call)(*placed), jax.jit(grads)(*placed)
    np.testing.assert_array_equal(np.asarray(four), np.asarray(one))
    for g, want in zip(four_grads, one_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("why,sq,sk,blocks", [
    ("a block that does not divide the sequence", 200, 200, {"block_q": 128}),
    ("queries that begin at a table row off the sublanes", 124, 256,
     {"block_q": 124, "block_k": 128}),
])
def test_latent_parts_fall_back_to_the_whole_operands(why, sq, sk, blocks):
    """Where the kernels cannot take the parts, q, k, v are put together in
    XLA and `flash_attention` takes them (its own XLA path, or the
    whole-operand kernels): the same values and gradients, a plan without
    the parts' word."""
    nope, r, e = 128, 64, 128
    q, kv, k_pe, w = _latent_operands(1, sq, sk, 2, nope, r, e)
    rope = _rope_tables(1, sk, r)
    before = attn.dispatch.taken()
    out, grads = _grads_and_value(
        lambda q, kv, k_pe: attn.latent_flash_attention(
            q, kv, k_pe, rope, **blocks), q, kv, k_pe, w)
    new = _new_plans(before)
    assert not any("latent_parts" in p for p in new["flash_attention.plan"])
    ref, ref_grads = _grads_and_value(_latent_assembled(rope, nope),
                                      q, kv, k_pe, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for g, want in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   atol=5e-4, rtol=5e-4)


def test_latent_parts_are_seen_in_the_operands_and_read_where_they_lie():
    """The parts are told by their ranks (a 3-D rotary key where the
    values would be): the forward takes q [bh, s, d] second (the face the
    benchmark's reader finds) and returns [bh, s, e]; kv goes in as the
    projection lays it, [b, t, h x (nope + e)], read a head's columns at a
    time by block index (row, 0, head); the one rotary key and the tables
    are read whatever the head; the backward reads do as [b, s, h x e] and
    out as the forward gave it (delta is made inside) and writes dkv laid
    as kv."""
    b, h, s, nope, r, e = 2, 2, 256, 128, 64, 128
    q, kv, k_pe, _ = _latent_operands(b, s, s, h, nope, r, e, jnp.bfloat16)
    rope = _rope_tables(b, s, r)

    def loss(q, kv, k_pe):
        return attn.latent_flash_attention(
            q, kv, k_pe, rope, block_q=128,
            block_k=128).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, k_pe)
    fwd, bwd = sorted(_pallas_calls(jaxpr.jaxpr),
                      key=lambda c: len(c.outvars))
    assert [v.aval.shape for v in fwd.invars] == [
        (2,), (b * h, s, nope + r), (b, s, h * (nope + e)), (b, s, r),
        (b, s, r), (b, s, r)]
    assert [v.aval.shape for v in fwd.outvars] == [(b * h, s, e),
                                                   (b * h, 8, s)]
    assert [v.aval.shape for v in bwd.invars] == [
        (2,), (b * h, s, nope + r), (b, s, h * (nope + e)), (b, s, r),
        (b, s, h * e), (b * h, 8, s), (b * h, 8, s), (b, s, r), (b, s, r),
        (b * h, s, e)]
    assert [v.aval.shape for v in bwd.outvars] == [
        (b * h, s, nope + r), (b, s, h * (nope + e)), (b * h, s, r)]
    assert fwd.params["grid_mapping"].num_scratch_operands == 2
    assert bwd.params["grid_mapping"].num_scratch_operands == 4
    offs = jnp.zeros((2,), jnp.int32)

    def block_index(call, operand, g, i):
        index = call.params["grid_mapping"].block_mappings[
            operand].index_map_jaxpr
        return [int(x) for x in jax.core.eval_jaxpr(
            index.jaxpr, index.consts, jnp.int32(g), jnp.int32(i), offs)]

    for g in range(b * h):
        for i in range(2):
            # block_mappings leave the prefetched scalars out
            assert block_index(fwd, 1, g, i) == [g // h, 0, g % h]    # kv
            assert block_index(fwd, 2, g, i) == [g // h, 0, 0]        # k_pe
            assert block_index(fwd, 3, g, i) == [g // h, 0, 0]        # cos
            assert block_index(bwd, 1, g, i) == [g // h, i, g % h]
            assert block_index(bwd, 2, g, i) == [g // h, i, 0]
            assert block_index(bwd, 3, g, i) == [g // h, 0, g % h]    # do
            assert block_index(bwd, 8, g, i) == [g, 0, 0]             # out
            assert block_index(bwd, 9, g, i) == [g, 0, 0]             # dq
            assert block_index(bwd, 10, g, i) == [g // h, i, g % h]   # dkv
            assert block_index(bwd, 11, g, i) == [g, i, 0]            # dk_pe
