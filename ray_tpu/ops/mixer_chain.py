"""The elementwise chains round a recurrent mixer's kernels, as Pallas passes
over tiles of rows with backward passes of their own, and the XLA
formulations elsewhere.  Three ops, one algorithm (a tile of rows, a halo,
the taps as shifted slices, SiLU's sigmoid as one tanh, the walk back);
what differs is what the caller passes: a bias or none, heads to norm or
widths to cut at.

  conv_silu_l2norm   the linear mixer's chain between W_qkv's product and
                     its rule's kernels (models/gdn_moe.py)
  conv_silu_split    the Mamba-2 mixer's chain between W_in's product and
                     the recurrence's kernels (models/ssd_moe.py): the
                     convolution WITH its bias, SiLU, the cut into x, B, C
  gated_group_norm   the gate and the norm by groups behind the recurrence

    conv_silu_l2norm(qkv [b, t, 2 hk dk + hv dv], conv_w [taps, channels],
                     key_heads = hk, d_k = dk, q_scale)
        -> q, k [b, t, hk dk], v [b, t, hv dv], in qkv's dtype:
    a_t = silu(sum_j conv_w[j] qkv[t - (taps - 1) + j])   (nothing before 0)
    q   = q_scale a[q's head] / sqrt(|a[q's head]|^2 + eps), k likewise at
          scale 1, v = a[v's columns]

    conv_silu_split(x [b, t, channels], conv_w [taps, channels], conv_b
                    [channels], widths) -> [b, t, w] for w in widths:
    a_t = silu(conv_b + sum_j conv_w[j] x[t - (taps - 1) + j]), cut in order

    gated_group_norm(y, z [b, t, columns], w [columns], groups, eps)
        -> g / sqrt(mean_group(g^2) + eps) w,  g = y silu(z): the gate
    BEFORE the norm, the mean square over each group's columns apart

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

`_split_forward_kernel`, `_split_backward_kernel`: the same tile, halo,
taps and walk with the bias added in front of the taps (one row block; its
gradient, dc's column sums, is one more row block of eight behind the
taps' in their resident block), no norm, and the cut at `widths`: a column
block (the first of `SPLIT_COLUMNS` that divides every width) belongs to
one array.  The backward takes the three cotangents as three operands and
writes the operand's ONCE: no concatenation is built.

`_norm_forward_kernel`, `_norm_backward_kernel`: no halo and no walk; a
column block is whole groups (a group a multiple of 128 lanes); a group's
sum of squares is its lane tiles added and ONE sum across lanes; float32
from y and z as they lie, rounded once where the result leaves (the XLA
formulation rounds the gated product first).  The backward takes y, z and
the cotangent alone, makes the tile's forward again, writes dy and dz once
and adds the weight's gradient into a resident float32 block of eight rows.

Off the kernels' shapes (a key head or a group no multiple of 128 columns,
a width no whole column block, rows no multiple of 16, over nine taps, a
mesh of several devices, neither a TPU nor the interpreter) the XLA
formulations: `_xla_chain` (`common.causal_depthwise_conv`, `jax.nn.silu`
and the l2 norm by whole tiles, as gdn_moe.py had them), `_xla_split` and
`_xla_gated_norm` (as ssd_moe.py had them).  `dispatch.taken()` says which
was traced: "mixer_chain" for the linear mixer's, "ssd_chain" for each of
the Mamba-2 mixer's two.
"""

from __future__ import annotations

import functools
import itertools

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
# columns a program of the chain with a bias, forward and backward, and
# groups a program of the gated norm: what the chip liked INSIDE the step
# program (v5e, 3 x 8192 x 6144 bfloat16, PERF.md, PR 49: the forward 1.65 ms
# at 256 and 1.66 at 512 columns, 1.70 at 1024; the backward 2.90 at 128,
# 3.05 at 256, 3.63 at 512; called alone every choice reads the same, 1.72
# and 2.98, as do the norm's 0.94 and 1.56 at one to four groups)
SPLIT_COLUMNS, SPLIT_BACKWARD_COLUMNS = (512, 256, 128), (128,)
NORM_GROUPS = (2, 1)


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


def _xla_split(x, conv_w, conv_b, widths):
    """The convolution in float32 from the operand as it lies, rounded once
    behind SiLU, as the mamba mixer had it."""
    from ray_tpu.models import common

    a = jax.nn.silu(common.causal_depthwise_conv(
        x.astype(F32), conv_w.astype(F32), conv_b.astype(F32))).astype(x.dtype)
    return tuple(jnp.split(a, list(itertools.accumulate(widths))[:-1],
                           axis=-1))


def _xla_gated_norm(y, z, w, groups, eps):
    """The gated product rounded to y's dtype, then the norm by whole tiles
    in float32, as the mamba mixer had it."""
    from ray_tpu.models import stack

    w = w.astype(F32).reshape(groups, 1, -1)
    gated = (y.astype(F32) * jax.nn.silu(z.astype(F32))).astype(y.dtype)
    return stack.per_head(gated, groups, lambda t: t * jax.lax.rsqrt(
        jnp.mean(t * t, axis=-1, keepdims=True) + eps) * w)


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


def _chain(x_ref, halo_ref, w_ref, first_tile, b_ref=None):
    """A tile's rows with the eight before them (float32), the convolution's
    result (from its bias [1, columns] where there is one), SiLU's sigmoid
    and result."""
    halo = halo_ref[...].astype(F32)[HALO - CARRY:]
    ext = jnp.concatenate([jnp.where(first_tile, 0.0, halo),
                           x_ref[...].astype(F32)], axis=0)
    rows, taps = x_ref.shape[0], w_ref.shape[0]
    c = 0.0 if b_ref is None else b_ref[...]
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


def _eight_sums(x):
    """A tile's rows summed down to eight partial sums a column."""
    return x.reshape(x.shape[0] // CARRY, CARRY, -1).sum(axis=0)


def _through_taps(dc, ext, w_ref, carry_ref, dx_ref, dw_ref):
    """dc, the cotangent at the convolution's result of a tile walked
    BACKWARD in time -> the operand's cotangent, written, and the taps'
    added into their resident block; the tile's first eight rows of dc are
    left in `carry_ref` for the tile before it."""
    rows, taps = dc.shape[0], w_ref.shape[0]
    later = jnp.concatenate([dc, carry_ref[...]], axis=0)
    carry_ref[...] = dc[:CARRY]
    dx = 0.0
    for tap in range(taps):
        shift = taps - 1 - tap
        dx = dx + later[shift:shift + rows] * w_ref[tap:tap + 1, :]
        partial = _eight_sums(dc * _shifted(ext, shift, rows))
        dw_ref[tap * CARRY:(tap + 1) * CARRY, :] += partial
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _backward_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref,
                     dx_ref, dw_ref, carry_ref, da_ref, *,
                     key_blocks, d_k, q_scale, eps):
    from jax.experimental import pallas as pl

    j, row, i = (pl.program_id(n) for n in range(3))
    tiles = pl.num_programs(2)

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
    _through_taps(dc, ext, w_ref, carry_ref, dx_ref, dw_ref)


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
# The chain with a bias and no norm, cut at any widths
# ---------------------------------------------------------------------------

def _firsts(widths, block: int):
    """The first column block of each array, and behind the last."""
    return (0, *itertools.accumulate(w // block for w in widths))


def _split_plan(x, widths, columns=SPLIT_COLUMNS):
    """(row tile, column block), or None where the shapes are not the
    kernels': every array whole blocks of whole lane tiles (the forward's
    choice exists where the backward's one lane tile does)."""
    rows = _row_tile(x.shape[1])
    block = next((c for c in columns if all(w % c == 0 for w in widths)),
                 None)
    return None if rows is None or block is None else (rows, block)


def _split_forward_kernel(x_ref, halo_ref, w_ref, b_ref, *out_refs, firsts):
    from jax.experimental import pallas as pl

    j = pl.program_id(0)
    a = _chain(x_ref, halo_ref, w_ref, pl.program_id(2) == 0, b_ref)[3]
    for n, out_ref in enumerate(out_refs):
        @pl.when((j >= firsts[n]) & (j < firsts[n + 1]))
        def _(out_ref=out_ref):
            out_ref[...] = a.astype(out_ref.dtype)


def _split_backward_kernel(x_ref, halo_ref, w_ref, b_ref, *rest, firsts):
    from jax.experimental import pallas as pl

    d_refs, (dx_ref, dw_ref, carry_ref) = rest[:-3], rest[-3:]
    j, row, i = (pl.program_id(n) for n in range(3))
    taps = w_ref.shape[0]

    @pl.when((row == 0) & (i == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    @pl.when(i == 0)            # behind a row's last position: nothing
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, carry_ref.dtype)

    ext, c, s, _ = _chain(x_ref, halo_ref, w_ref,
                          i == pl.num_programs(2) - 1, b_ref)
    da = d_refs[-1][...]        # the array this column block belongs to
    for n in reversed(range(len(d_refs) - 1)):
        da = jnp.where(j < firsts[n + 1], d_refs[n][...], da)
    dc = da.astype(F32) * (s * (1.0 + c * (1.0 - s)))
    _through_taps(dc, ext, w_ref, carry_ref, dx_ref, dw_ref)
    # the bias's gradient, dc's column sums, behind the taps' in their block
    dw_ref[taps * CARRY:, :] += _eight_sums(dc)


def _row_spec(block):
    """A [1, columns] row (a bias, a weight) by the grid's column block."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, block), lambda j, row, i: (0, j))


@functools.partial(jax.jit, static_argnums=(3,))
def _split_forward(x, conv_w, conv_b, widths):
    from jax.experimental import pallas as pl

    b, t, channels = x.shape
    rows, block = _split_plan(x, widths)
    firsts, tiles = _firsts(widths, block), t // rows
    return pl.pallas_call(
        functools.partial(_split_forward_kernel, firsts=firsts),
        grid=(channels // block, b, tiles),
        in_specs=_shared_specs(conv_w.shape[0], rows, block, lambda i: i)
        + [_row_spec(block)],
        out_specs=[pl.BlockSpec(
            (None, rows, block),
            _third(first, behind - first, b, tiles, lambda i: i))
            for first, behind in zip(firsts, firsts[1:])],
        out_shape=[jax.ShapeDtypeStruct((b, t, w), x.dtype) for w in widths],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="ssd_chain_fwd",
    )(x, x, conv_w.astype(F32), conv_b.astype(F32)[None])


@functools.partial(jax.jit, static_argnums=(4,))
def _split_backward(x, conv_w, conv_b, cotangents, widths):
    """-> (dx like x, dconv_w [taps, channels], dconv_b [channels], both
    float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, channels = x.shape
    taps = conv_w.shape[0]
    rows, block = _split_plan(x, widths, SPLIT_BACKWARD_COLUMNS)
    firsts, tiles = _firsts(widths, block), t // rows

    def back(i):
        return tiles - 1 - i

    dx, dw = pl.pallas_call(
        functools.partial(_split_backward_kernel, firsts=firsts),
        grid=(channels // block, b, tiles),
        in_specs=_shared_specs(taps, rows, block, back)
        + [_row_spec(block)] + [pl.BlockSpec(
            (None, rows, block),
            _third(first, behind - first, b, tiles, back))
            for first, behind in zip(firsts, firsts[1:])],
        out_specs=[pl.BlockSpec((None, rows, block),
                                lambda j, row, i: (row, back(i), j)),
                   pl.BlockSpec(((taps + 1) * CARRY, block),
                                lambda j, row, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(((taps + 1) * CARRY, channels), F32)],
        scratch_shapes=[pltpu.VMEM((CARRY, block), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="ssd_chain_bwd",
    )(x, x, conv_w.astype(F32), conv_b.astype(F32)[None], *cotangents)
    dw = dw.reshape(taps + 1, CARRY, channels).sum(axis=1)
    return dx, dw[:taps], dw[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_split(x, conv_w, conv_b, widths):
    return tuple(_split_forward(x, conv_w, conv_b, widths))


def _split_vjp_fwd(x, conv_w, conv_b, widths):
    return _pallas_split(x, conv_w, conv_b, widths), (x, conv_w, conv_b)


def _split_vjp_bwd(widths, res, cotangents):
    x, conv_w, conv_b = res
    dx, dw, db = _split_backward(x, conv_w, conv_b, tuple(cotangents),
                                 widths)
    return dx, dw.astype(conv_w.dtype), db.astype(conv_b.dtype)


_pallas_split.defvjp(_split_vjp_fwd, _split_vjp_bwd)


# ---------------------------------------------------------------------------
# The gate and the norm by groups behind a recurrence
# ---------------------------------------------------------------------------

def _norm_plan(y, groups: int):
    """(row tile, column block: whole groups of whole lane tiles), or None
    where the shapes are not the kernels'."""
    inner = y.shape[2]
    if inner % groups or (inner // groups) % 128:
        return None
    rows = _row_tile(y.shape[1])
    count = next((g for g in NORM_GROUPS if groups % g == 0), None)
    if rows is None or count is None:
        return None
    return rows, count * (inner // groups)


def _group_sum(x):
    """A group's columns summed, a row: its lane tiles added, then ONE sum
    across lanes."""
    tile = x[:, :128]
    for lane in range(128, x.shape[1], 128):
        tile = tile + x[:, lane:lane + 128]
    return _row_sum(tile)


def _gated(y, z):
    """y, z (float32), SiLU's sigmoid of z as one tanh, and y silu(z)."""
    y, z = y.astype(F32), z.astype(F32)
    s = 0.5 * jnp.tanh(0.5 * z) + 0.5
    return y, z, s, y * (z * s)


def _groups_of(ref, width: int):
    """The column slices of a block's groups."""
    return [slice(h, h + width) for h in range(0, ref.shape[1], width)]


def _norm_forward_kernel(y_ref, z_ref, w_ref, o_ref, *, width, eps):
    for cols in _groups_of(y_ref, width):
        g = _gated(y_ref[:, cols], z_ref[:, cols])[3]
        r = jax.lax.rsqrt(_group_sum(g * g) * (1.0 / width) + eps)
        o_ref[:, cols] = (g * r * w_ref[:, cols]).astype(o_ref.dtype)


def _norm_backward_kernel(y_ref, z_ref, w_ref, do_ref, dy_ref, dz_ref,
                          dw_ref, *, width, eps):
    from jax.experimental import pallas as pl

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    for cols in _groups_of(y_ref, width):
        y, z, s, g = _gated(y_ref[:, cols], z_ref[:, cols])
        do = do_ref[:, cols].astype(F32)
        r = jax.lax.rsqrt(_group_sum(g * g) * (1.0 / width) + eps)
        n, dn = g * r, do * w_ref[:, cols]
        dw_ref[:, cols] += _eight_sums(do * n)
        dg = r * (dn - n * (_group_sum(dn * n) * (1.0 / width)))
        dy_ref[:, cols] = (dg * (z * s)).astype(dy_ref.dtype)
        dz_ref[:, cols] = (dg * y * (s * (1.0 + z * (1.0 - s)))).astype(
            dz_ref.dtype)


def _tile_spec(rows, block):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, rows, block), lambda j, row, i: (row, i, j))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _norm_forward(y, z, w, groups, eps):
    from jax.experimental import pallas as pl

    b, t, inner = y.shape
    rows, block = _norm_plan(y, groups)
    return pl.pallas_call(
        functools.partial(_norm_forward_kernel, width=inner // groups,
                          eps=eps),
        grid=(inner // block, b, t // rows),
        in_specs=[_tile_spec(rows, block)] * 2 + [_row_spec(block)],
        out_specs=_tile_spec(rows, block),
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="gated_norm_fwd",
    )(y, z, w.astype(F32)[None])


@functools.partial(jax.jit, static_argnums=(4, 5))
def _norm_backward(y, z, w, do, groups, eps):
    """-> (dy, dz like y, dw [columns] float32)."""
    from jax.experimental import pallas as pl

    b, t, inner = y.shape
    rows, block = _norm_plan(y, groups)
    dy, dz, dw = pl.pallas_call(
        functools.partial(_norm_backward_kernel, width=inner // groups,
                          eps=eps),
        grid=(inner // block, b, t // rows),
        in_specs=[_tile_spec(rows, block)] * 2 + [_row_spec(block),
                                                  _tile_spec(rows, block)],
        out_specs=[_tile_spec(rows, block)] * 2 + [
            pl.BlockSpec((CARRY, block), lambda j, row, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((CARRY, inner), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="gated_norm_bwd",
    )(y, z, w.astype(F32)[None], do)
    return dy, dz, dw.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_norm(y, z, w, groups, eps):
    return _norm_forward(y, z, w, groups, eps)


def _norm_vjp_fwd(y, z, w, groups, eps):
    return _pallas_norm(y, z, w, groups, eps), (y, z, w)


def _norm_vjp_bwd(groups, eps, res, do):
    y, z, w = res
    dy, dz, dw = _norm_backward(y, z, w, do, groups, eps)
    return dy, dz, dw.astype(w.dtype)


_pallas_norm.defvjp(_norm_vjp_fwd, _norm_vjp_bwd)


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

def _way(fits: bool) -> str:
    """Which way a call goes whose shapes fit the kernels or do not:
    "pallas", "interpret" or "xla"."""
    interpret = dispatch.interpret_mode()
    mesh = jax.sharding.get_abstract_mesh()
    if (not fits
            or not (mesh is None or mesh.empty or mesh.size == 1)
            or not (interpret or dispatch.platform() == "tpu")):
        return "xla"
    return "interpret" if interpret else "pallas"


def path(qkv, conv_w, key_heads: int, d_k: int) -> str:
    """Which way a call of `conv_silu_l2norm` goes."""
    return _way(_plan(qkv, key_heads, d_k) is not None
                and conv_w.shape[0] - 1 <= CARRY)


def split_path(x, conv_w, widths) -> str:
    """Which way a call of `conv_silu_split` goes."""
    return _way(_split_plan(x, widths) is not None
                and conv_w.shape[0] - 1 <= CARRY)


def norm_path(y, groups: int) -> str:
    """Which way a call of `gated_group_norm` goes."""
    return _way(_norm_plan(y, groups) is not None)


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


def conv_silu_split(x, conv_w, conv_b, widths):
    """x [b, t, channels] as a projection's product lays it; conv_w [taps,
    channels], conv_b [channels]; widths, the arrays' columns in order ->
    silu(conv_b + the causal convolution) cut into [b, t, w] for w in
    widths, in x's dtype."""
    widths = tuple(int(w) for w in widths)
    if conv_w.shape[1] != x.shape[2] or conv_b.shape != x.shape[2:] \
            or sum(widths) != x.shape[2]:
        raise ValueError(f"conv_w {conv_w.shape}, conv_b {conv_b.shape} and "
                         f"widths {widths} for x {x.shape}")
    taken = split_path(x, conv_w, widths)
    dispatch.record("ssd_chain", taken)
    if taken == "xla":
        return _xla_split(x, conv_w, conv_b, widths)
    return _pallas_split(x, conv_w, conv_b, widths)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """y, z [b, t, columns], w [columns] -> rmsnorm_g(y silu(z)) w, the
    mean square over each of `groups` groups of columns apart, in y's
    dtype."""
    if z.shape != y.shape or w.shape != y.shape[2:]:
        raise ValueError(f"z {z.shape} and w {w.shape} for y {y.shape}")
    taken = norm_path(y, groups)
    dispatch.record("ssd_chain", taken)
    if taken == "xla":
        return _xla_gated_norm(y, z, w, groups, eps)
    return _pallas_norm(y, z, w, groups, float(eps))
