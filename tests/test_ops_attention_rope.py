"""Rope inside the flash kernels (rope=; CPU interpret mode): q and k as
projected, roped where the tiles are loaded; against rope in XLA before the
same kernels.  A part of tests/test_ops_attention.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attn
from attention_cases import (  # noqa: F401 (the fixture is autouse)
    _grads_and_value, _interpret_mode, _masked_reference, _new_plans,
    _pallas_calls, _rand_qkv, _rope_tables)


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
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w32), argnums=(0, 1, 2)))(*xs)

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
