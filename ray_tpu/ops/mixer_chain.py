"""The chain between a linear mixer's W_qkv product and its rule's kernels:
a causal depthwise convolution, SiLU, an l2 norm a head for q and k, and the
cut into the three arrays the kernels take: one Pallas pass over tiles of
rows, with a backward pass of its own, and the XLA formulation elsewhere.

    conv_silu_l2norm(qkv [b, t, 2 hk dk + hv dv], conv_w [taps, channels],
                     key_heads = hk, d_k = dk, q_scale)
        -> q, k [b, t, hk dk], v [b, t, hv dv], in qkv's dtype:
    a_t = silu(sum_j conv_w[j] qkv[t - (taps - 1) + j])   (nothing before 0)
    q   = q_scale a[q's head] / sqrt(|a[q's head]|^2 + eps), k likewise at
          scale 1, v = a[v's columns]

The kernels (`_forward_kernel`, `_backward_kernel`).  Grid (column blocks,
batch, row tiles); a program holds a tile of `_row_tile` rows by a block of
whole key heads' columns (a head is a multiple of 128 lanes, so a norm's sum
stays inside its lane tiles) and computes the whole chain in float32 from
qkv as it lies, rounding ONCE where q, k and v leave (the XLA formulation
multiplies the taps and takes SiLU in qkv's dtype).  qkv is read once and q,
k, v written once: a column block belongs to exactly one of the three
arrays, whose index map stands still while the grid walks the other two
arrays' columns (`_third`), so a block of an output is visited in ONE run of
steps and written in one of them.  The convolution's three earlier rows come
as a halo block of qkv itself (the 16 rows before the tile; zero at position
0 of a row of the batch: nothing crosses rows of the batch).

The backward takes dq, dk, dv and qkv, the ONLY residual: it computes the
tile's chain again, pushes the cotangent through the norms, SiLU and the
taps, writes dqkv once and adds the taps' gradient into a float32 block
[taps x 8, columns] that stays resident while the grid walks a column
block's batch rows and row tiles (eight partial sums a tap, added outside).
It walks time BACKWARD and carries the first eight rows of the later tile's
cotangent at the convolution's output in a VMEM scratch (the taps send a
row's cotangent to the three rows before it).

Off the kernels' shapes (a key head no multiple of 128 columns, rows no
multiple of 16, a mesh of several devices, neither a TPU nor the
interpreter) `_xla_chain`: `common.causal_depthwise_conv`, `jax.nn.silu` and
the l2 norm by whole tiles, as the model file had them.
`dispatch.taken()["mixer_chain"]` says which was traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch

F32 = jnp.float32
L2_EPS = 1e-6
HALO = 16           # rows of the halo block: a bfloat16 tile's rows
CARRY = 8           # rows carried between tiles: a float32 tile's rows
ROW_TILES = (512, 256, 128, 64, 32, 16)
# key heads a program: what the chip liked (v5e, 3 x 8192 x 8192 bfloat16,
# PERF.md, PR 46: the forward 2.49 ms at two heads and 2.84 at one, the
# backward 4.75 and 4.42; four heads and tiles of 256 and 1024 rows no
# faster)
FORWARD_HEADS, BACKWARD_HEADS = (2, 1), (1,)


# ---------------------------------------------------------------------------
# XLA formulation: the path off the kernels' shapes
# ---------------------------------------------------------------------------

def _l2_normalised(x, heads: int, scale: float = 1.0, eps: float = L2_EPS):
    """x [b, s, heads x w] with every head's w columns scaled to length
    `scale`, in float32 by whole tiles, in x's dtype."""
    from ray_tpu.models import common

    t = common.by_tiles(x, heads).astype(F32)
    t = t * (scale * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + eps))
    return common.from_tiles(t.astype(x.dtype))


def _xla_chain(qkv, conv_w, key_heads, d_k, q_scale, eps):
    from ray_tpu.models import common

    keys = key_heads * d_k
    a = jax.nn.silu(common.causal_depthwise_conv(qkv, conv_w))
    return (_l2_normalised(a[..., :keys], key_heads, q_scale, eps),
            _l2_normalised(a[..., keys:2 * keys], key_heads, 1.0, eps),
            a[..., 2 * keys:])


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _row_tile(t: int):
    return next((r for r in ROW_TILES if t % r == 0), None)


def _column_block(keys: int, values: int, d_k: int, heads):
    """Whole key heads a program, the first of `heads` that cuts q's, k's
    and v's columns into whole blocks."""
    return next((h * d_k for h in heads
                 if keys % (h * d_k) == 0 and values % (h * d_k) == 0), None)


def _shifted(ext, shift: int, rows: int):
    """Rows -shift .. rows - shift - 1 of a tile whose rows -CARRY .. -1
    lie in front of it in `ext`."""
    return ext[CARRY - shift:CARRY - shift + rows]


def _chain(x_ref, halo_ref, w_ref, first_tile):
    """A tile's rows with the eight before them (float32), the convolution's
    result, SiLU's sigmoid and result."""
    halo = halo_ref[...].astype(F32)[HALO - CARRY:]
    ext = jnp.concatenate([jnp.where(first_tile, 0.0, halo),
                           x_ref[...].astype(F32)], axis=0)
    rows, taps = x_ref.shape[0], w_ref.shape[0]
    c = 0.0
    for j in range(taps):
        c = c + _shifted(ext, taps - 1 - j, rows) * w_ref[j:j + 1, :]
    # the sigmoid as ONE transcendental (1 / (1 + exp(-c)) is an exp and a
    # division: the chain is bound by the vector unit, PERF.md, PR 46)
    s = 0.5 * jnp.tanh(0.5 * c) + 0.5
    return ext, c, s, c * s


def _row_sum(x):      # a head's columns summed, a row
    return jnp.sum(x, axis=1, keepdims=True)


def _per_head(d_k: int, fn, *arrays):
    """fn over every head's d_k columns of the arrays, side by side."""
    width = arrays[0].shape[1]
    return jnp.concatenate(
        [fn(*(a[:, h:h + d_k] for a in arrays))
         for h in range(0, width, d_k)], axis=1)


def _forward_kernel(x_ref, halo_ref, w_ref, q_ref, k_ref, v_ref, *,
                    key_blocks, d_k, q_scale, eps):
    from jax.experimental import pallas as pl

    j = pl.program_id(0)
    _, _, _, a = _chain(x_ref, halo_ref, w_ref, pl.program_id(2) == 0)

    @pl.when(j < 2 * key_blocks)
    def _():
        scale = jnp.where(j < key_blocks, q_scale, 1.0).astype(F32)
        n = _per_head(d_k, lambda h: h * (scale * jax.lax.rsqrt(
            _row_sum(h * h) + eps)), a)

        @pl.when(j < key_blocks)
        def _():
            q_ref[...] = n.astype(q_ref.dtype)

        @pl.when(j >= key_blocks)
        def _():
            k_ref[...] = n.astype(k_ref.dtype)

    @pl.when(j >= 2 * key_blocks)
    def _():
        v_ref[...] = a.astype(v_ref.dtype)


def _backward_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref,
                     dx_ref, dw_ref, carry_ref, da_ref, *,
                     key_blocks, d_k, q_scale, eps):
    from jax.experimental import pallas as pl

    j, row, i = (pl.program_id(n) for n in range(3))
    tiles = pl.num_programs(2)
    rows, taps = x_ref.shape[0], w_ref.shape[0]

    @pl.when((row == 0) & (i == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    @pl.when(i == 0)            # behind a row's last position: nothing
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, carry_ref.dtype)

    ext, c, s, a = _chain(x_ref, halo_ref, w_ref, i == tiles - 1)

    @pl.when(j < 2 * key_blocks)
    def _():
        scale = jnp.where(j < key_blocks, q_scale, 1.0).astype(F32)
        g = jnp.where(j < key_blocks, dq_ref[...], dk_ref[...]).astype(F32)

        def through_norm(g, a):
            r = jax.lax.rsqrt(_row_sum(a * a) + eps)
            n = a * r
            return (scale * r) * (g - n * _row_sum(g * n))

        da_ref[...] = _per_head(d_k, through_norm, g, a)

    @pl.when(j >= 2 * key_blocks)
    def _():
        da_ref[...] = dv_ref[...].astype(F32)

    dc = da_ref[...] * (s * (1.0 + c * (1.0 - s)))
    later = jnp.concatenate([dc, carry_ref[...]], axis=0)
    carry_ref[...] = dc[:CARRY]
    dx = 0.0
    for tap in range(taps):
        shift = taps - 1 - tap
        dx = dx + later[shift:shift + rows] * w_ref[tap:tap + 1, :]
        partial = (dc * _shifted(ext, shift, rows)).reshape(
            rows // CARRY, CARRY, -1).sum(axis=0)
        dw_ref[tap * CARRY:(tap + 1) * CARRY, :] += partial
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _third(first: int, count: int, batch: int, tiles: int, time_of):
    """The index map of the array that holds column blocks first .. first +
    count - 1 of the channels, for the grid (column block, batch row, step)
    walking time tile `time_of(step)`: before its blocks it waits at its
    first block, behind them it stays at its last."""

    def index(j, row, i):
        before, behind = j < first, j >= first + count

        def pick(lo, mid, hi):
            return jnp.where(before, lo, jnp.where(behind, hi, mid))

        return (pick(0, row, batch - 1),
                pick(time_of(0), time_of(i), time_of(tiles - 1)),
                pick(0, j - first, count - 1))

    return index


def _plan(qkv, key_heads: int, d_k: int, heads=FORWARD_HEADS):
    """(row tile, column block, q's and k's blocks, v's blocks, v's width),
    or None where the shapes are not the kernels'."""
    b, t, channels = qkv.shape
    keys = key_heads * d_k
    values = channels - 2 * keys
    if d_k % 128 or values <= 0 or values % 128:
        return None
    rows, block = _row_tile(t), _column_block(keys, values, d_k, heads)
    if rows is None or block is None:
        return None
    return rows, block, keys // block, values // block, values


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=48 << 20)


def _shared_specs(taps, rows, block, time_of):
    from jax.experimental import pallas as pl

    def halo(j, row, i):    # the HALO rows before the tile; never read at 0
        return row, jnp.maximum(time_of(i) * (rows // HALO) - 1, 0), j

    return [pl.BlockSpec((None, rows, block),
                         lambda j, row, i: (row, time_of(i), j)),
            pl.BlockSpec((None, HALO, block), halo),
            pl.BlockSpec((taps, block), lambda j, row, i: (0, j))]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _forward(qkv, conv_w, key_heads, d_k, q_scale, eps):
    from jax.experimental import pallas as pl

    b, t, channels = qkv.shape
    rows, block, key_blocks, value_blocks, values = _plan(qkv, key_heads, d_k)
    tiles = t // rows

    def third(first, count):
        return pl.BlockSpec((None, rows, block),
                            _third(first, count, b, tiles, lambda i: i))

    return pl.pallas_call(
        functools.partial(_forward_kernel, key_blocks=key_blocks, d_k=d_k,
                          q_scale=q_scale, eps=eps),
        grid=(channels // block, b, tiles),
        in_specs=_shared_specs(conv_w.shape[0], rows, block, lambda i: i),
        out_specs=[third(0, key_blocks), third(key_blocks, key_blocks),
                   third(2 * key_blocks, value_blocks)],
        out_shape=[jax.ShapeDtypeStruct((b, t, w), qkv.dtype)
                   for w in (key_heads * d_k, key_heads * d_k, values)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="mixer_chain_fwd",
    )(qkv, qkv, conv_w.astype(F32))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _backward(qkv, conv_w, dq, dk, dv, key_heads, d_k, q_scale, eps):
    """-> (dqkv like qkv, dconv_w [taps, channels] float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, channels = qkv.shape
    taps = conv_w.shape[0]
    rows, block, key_blocks, value_blocks, _ = _plan(qkv, key_heads, d_k,
                                                     BACKWARD_HEADS)
    tiles = t // rows

    def back(i):
        return tiles - 1 - i

    def third(first, count):
        return pl.BlockSpec((None, rows, block),
                            _third(first, count, b, tiles, back))

    dx, dw = pl.pallas_call(
        functools.partial(_backward_kernel, key_blocks=key_blocks, d_k=d_k,
                          q_scale=q_scale, eps=eps),
        grid=(channels // block, b, tiles),
        in_specs=_shared_specs(taps, rows, block, back) + [
            third(0, key_blocks), third(key_blocks, key_blocks),
            third(2 * key_blocks, value_blocks)],
        out_specs=[pl.BlockSpec((None, rows, block),
                                lambda j, row, i: (row, back(i), j)),
                   pl.BlockSpec((taps * CARRY, block),
                                lambda j, row, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                   jax.ShapeDtypeStruct((taps * CARRY, channels), F32)],
        scratch_shapes=[pltpu.VMEM((CARRY, block), F32),
                        pltpu.VMEM((rows, block), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="mixer_chain_bwd",
    )(qkv, qkv, conv_w.astype(F32), dq, dk, dv)
    return dx, dw.reshape(taps, CARRY, channels).sum(axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _pallas_chain(qkv, conv_w, key_heads, d_k, q_scale, eps):
    return tuple(_forward(qkv, conv_w, key_heads, d_k, q_scale, eps))


def _chain_vjp_fwd(qkv, conv_w, key_heads, d_k, q_scale, eps):
    return _pallas_chain(qkv, conv_w, key_heads, d_k, q_scale, eps), (
        qkv, conv_w)


def _chain_vjp_bwd(key_heads, d_k, q_scale, eps, res, cotangents):
    qkv, conv_w = res
    dx, dw = _backward(qkv, conv_w, *cotangents, key_heads, d_k, q_scale,
                       eps)
    return dx, dw.astype(conv_w.dtype)


_pallas_chain.defvjp(_chain_vjp_fwd, _chain_vjp_bwd)


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

def path(qkv, conv_w, key_heads: int, d_k: int) -> str:
    """Which way a call goes: "pallas", "interpret" or "xla"."""
    interpret = dispatch.interpret_mode()
    mesh = jax.sharding.get_abstract_mesh()
    if (_plan(qkv, key_heads, d_k) is None
            or conv_w.shape[0] - 1 > CARRY
            or not (mesh is None or mesh.empty or mesh.size == 1)
            or not (interpret or dispatch.platform() == "tpu")):
        return "xla"
    return "interpret" if interpret else "pallas"


def conv_silu_l2norm(qkv, conv_w, key_heads: int, d_k: int, q_scale: float,
                     eps: float = L2_EPS):
    """qkv [b, t, 2 x key_heads x d_k + value columns] as W_qkv's product
    lays it; conv_w [taps, channels] -> (q, k [b, t, key_heads x d_k], v
    [b, t, value columns]) as the module's header says, in qkv's dtype."""
    if conv_w.shape[1] != qkv.shape[2]:
        raise ValueError(f"conv_w {conv_w.shape} for qkv {qkv.shape}")
    taken = path(qkv, conv_w, key_heads, d_k)
    dispatch.record("mixer_chain", taken)
    if taken == "xla":
        return _xla_chain(qkv, conv_w, key_heads, d_k, q_scale, eps)
    return _pallas_chain(qkv, conv_w, key_heads, d_k, float(q_scale),
                         float(eps))
