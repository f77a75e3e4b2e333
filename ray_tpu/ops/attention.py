"""Attention ops: Pallas TPU flash attention (fwd + bwd) with a jnp fallback.

The reference framework ships no attention kernels (SURVEY.md §5 — long-context
machinery is absent in-tree); on TPU this is a core op.  Design:

  - `flash_attention(q, k, v, causal=..., window=...)`: online-softmax
    tiled kernel (Pallas, grid over (batch x groups of heads, q-tiles), the
    K/V of a program's heads resident in VMEM) so the s×s score matrix
    never materializes in HBM.
    Causal, or causal under a sliding window (query t sees keys s with
    0 <= t - s < window): the blocks wholly behind a tile's window are not
    visited, the trailing edge is masked in forward and backward.
  - `flash_attention_chunk(...)`: the offset-aware variant returning
    (out, lse) — the building block ring attention uses per K/V chunk
    (ops/ring_attention.py); positions enter as DYNAMIC scalars so the
    same compiled kernel serves every ring step.
  - Backward: ONE Pallas kernel (grid over k-tiles) that recomputes the
    scores blockwise from the saved logsumexp once and gives dq, dk and
    dv from them; the s×s matrix never exists in the backward either.
    The lse OUTPUT is differentiable too (ring attention's merge weights
    depend on it): ds += p * dlse.
  - One plan for the two kernels, made from what each can see (head
    size, sm_scale, the offsets): a program's long tile meets the blocks
    wholly under the diagonal in a loop and the blocks the diagonal
    crosses in straight-line steps against only the part of the tile
    that can see them, so few scores above the diagonal are computed;
    scores are held [keys, queries], so softmax reduces down sublanes;
    a power-of-two sm_scale (head size 64) is folded into the operand
    tile, which is exact; tile and block sizes come from `default_blocks`
    unless passed.  The comment above `_scale_is_exact` has the reasons,
    `dispatch.taken()["flash_attention.plan"]` what ran.
  - `rope=(cos, sin)`: the kernels apply the rotary embedding themselves,
    to the q and k tiles as they load them and its transpose to the
    float32 sums of dq and dk before their one rounding, so q and k go
    from their projections to the call un-roped and no float32 copy of
    either crosses HBM (`flash_attention`'s docstring; the XLA paths rope
    with `rope_reference`).
  - CPU / odd-shape fallback: `attention_reference` with identical
    semantics — the numerical ground truth in tests (which compare both
    paths in interpret mode, values and grads).

  - Values of another width than the keys (latent attention in training:
    keys 192 wide, values 128): the same two kernels, whose output,
    accumulator, do and dv take the values' width and whose scores contract
    over the keys'.  Nothing is padded; equal widths build the kernels as
    they were.
  - Latent attention IN PARTS (`latent_flash_attention`): q un-roped, a
    head's [k_nope | v] as its projection lays it, ONE rotary key shared
    by all heads.  The kernels read a head's block of the projection's
    output by lane-block index, rope the last columns of q and the one
    rotary key in VMEM and put the keys together there, so the
    concatenate, split, broadcast and rope passes never cross HBM; the
    backward writes [dk_nope | dv] as the projection's gradient reads it.

Layout convention: q, k are [batch, seq, heads, head_dim], v is [batch, seq,
heads, value_dim] (the models/ convention).  The whole-operand calls hand
the kernels q, k, v (and do) as [batch, seq, heads x head_dim], which is how
the projections' matmuls write them and W_o and the weight gradients read
out, dq, dk and dv: a reshape that XLA folds against the model's own, no
transpose and no 64-wide last axis (half a lane block, which XLA pads to
twice its bytes and lays sequence-minor).  The grid's first axis selects
LANE blocks through the BlockSpecs' index maps: one head of 128 is one lane
block, two heads of 64 share one, lcm(d, 128) / d in general
(`_heads_a_program`).  A program works its heads together: loads and stores
are whole lane blocks; each head's scores come from a contraction over all
the block's lanes against an operand in which the other heads' lanes are
zero (`_head_alone`: the MXU passes of the d-deep contraction, no lane
shift); softmax's state, the output's accumulator and dq's sum are a
head's own rows, dk's and dv's sums a head's own array, joined by lane at
the end.  The plan says `operands_bshd,heads2x64`.  lse, and the -dlse the
backward takes, stay a row a head, [batch x heads, 8, seq].
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import dispatch

_NEG_INF = float(-1e30)


def _can_use_pallas(seq_q: int, seq_k: int, head_dim: int,
                    block_q: int, block_k: int,
                    value_dim: Optional[int] = None) -> bool:
    if dispatch.interpret_mode():
        return seq_q % block_q == 0 and seq_k % block_k == 0
    return (
        dispatch.platform() == "tpu"
        and seq_q % block_q == 0
        and seq_k % block_k == 0
        and head_dim % 64 == 0
        and (value_dim or head_dim) % 64 == 0
    )


# ---------------------------------------------------------------------------
# Reference (jnp) path — also the numerical ground truth in tests.
# ---------------------------------------------------------------------------

def attention_reference(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Plain attention. q:[b,s,h,d] k:[b,t,h,d] v:[b,t,h,e] -> [b,s,h,e].
    With a window (causal only) query t sees keys s with 0 <= t - s <
    window."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        # Align ends: query i attends keys j where j - (sk - sq) <= i.
        mask = (jnp.arange(sk)[None, :] - (sk - sq)
                <= jnp.arange(sq)[:, None])
        if window is not None:
            mask = mask & (jnp.arange(sk)[None, :] - (sk - sq)
                           > jnp.arange(sq)[:, None] - window)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# The plan: what each kernel decides from what it can see
# ---------------------------------------------------------------------------
# Scalar-prefetch arg offs = [q_off, kv_off] (+ [window] under a sliding
# window): global position of this operand's row/col 0.  The plain causal call uses (sk - sq, 0) (ends
# aligned); ring attention passes each chunk's global offsets, so one
# compiled kernel serves every ring step (fully-unmasked, diagonal, and
# fully-masked chunks alike).
#
# What the v5e charges for at head size 64 (PERF.md, PR 29): the MXU,
# whose tiles a 64-wide contraction or result half fills, and the
# cross-LANE reductions of softmax.  The mask and the scale are free (the
# vector slots they take are idle anyway).  So:
#
#   - A program's tile is `tile` long and is worked against blocks of
#     `inner` of the other operand, tile = R * inner.  Blocks wholly under
#     the diagonal meet the whole tile in a loop; the R - 1 blocks the
#     diagonal then crosses are straight-line steps, each against only the
#     part of the tile that can see it (a static slice), so the scores
#     above the diagonal are mostly never computed.  Which blocks those
#     are comes from `offs` (`_first_narrow_block`), so the offsets may be
#     traced and lie off the block grid; every step masks, and a step whose
#     block lies outside the operand is masked whole.
#   - Both kernels hold scores TRANSPOSED, [keys, queries]: softmax's
#     max and sum then run down sublanes (plain vector max / add), lse,
#     delta and dlse broadcast as they are stored, and p^T · do, ds^T · q
#     need no transposed operand.  The forward's accumulator is held
#     [d, queries] and turned once a program, the backward's sum of dq
#     [d, queries] and turned once a head.
#   - A power-of-two scale (`_scale_is_exact`: 1/sqrt(64) = 0.125) leaves
#     the score tile: scaling a bf16 or f32 value by a power of two only
#     moves its exponent, so (q·scale)·k^T equals (q·k^T)·scale bit for
#     bit.  The [tile, d] operand is scaled once a program (in the
#     backward k, which then carries the scale into dq as well), ds stays
#     unscaled and the f32 accumulator of dk is scaled at the end.
#     Any other scale (head size 128) keeps its per-score multiplies.
#   - Tile and block sizes come from `default_blocks` (head size, lengths,
#     dtype) unless the caller passes block_q / block_k, which then hold
#     for both kernels.

def _scale_is_exact(sm_scale: float) -> bool:
    """True when sm_scale is a power of two, so it commutes with every
    rounding between the operand tile and the accumulator."""
    return sm_scale > 0 and math.frexp(sm_scale)[0] == 0.5


def _tile_and_inner(seq_tile: int, seq_inner: int):
    """The longest tile up to 2048 that divides seq_tile, against blocks
    of 512; where the lengths do not divide so, the old single size
    min(512, length) for each."""
    inner = min(512, seq_inner)
    tile = next((t for t in (2048, 1024, 512)
                 if seq_tile % t == 0 and t % inner == 0), None)
    return (tile or min(512, seq_tile)), inner


def default_blocks(head_dim: int, seq_q: int, seq_k: int, dtype,
                   window: Optional[int] = None):
    """((block_q, block_k) of the forward, of the backward) for a caller
    that passes none, from the v5e's sweep at sequence 2048, bf16, head
    sizes 64 and 128 (PERF.md, PR 29).  The forward tiles the queries,
    the backward the keys.  Blocks of 256 or 128 leave fewer
    scores above the diagonal and run 5-10 % faster, but each block is one
    more straight-line step to trace in every process that builds the
    kernel, and a train worker's start pays for that (PERF.md).

    Under a window shorter than the keys a long tile would meet every
    block of its window with all its queries, most of which cannot see
    it: tile and block are both 512 then, so a tile visits the block on
    its diagonal and the one behind it."""
    del dtype                  # the sweep gave one answer for those it ran
    if window is not None and window < seq_k:
        short = (min(512, seq_q), min(512, seq_k))
        return short, short
    kv_tile, q_inner = _tile_and_inner(seq_k, seq_q)
    if _one_wide_head(_heads_a_program(head_dim, head_dim), head_dim):
        # the backward's k, v, dk and dv tiles, its two float32 sums and the
        # four [keys, queries] tiles of a step are 40 MiB at a key tile of
        # 2048 x 256 beside the head's whole q, do, out and dq, and the chip
        # has 128 (PERF.md, PR 42)
        kv_tile = min(kv_tile, 1024)
    return _tile_and_inner(seq_q, seq_k), (q_inner, kv_tile)


def _narrow_steps(tile: int, inner: int) -> int:
    """R - 1: how many blocks on the diagonal meet less than the tile."""
    return tile // inner - 1 if tile % inner == 0 else 0


def _first_narrow_block(tile_min, inner: int):
    """Index of the first block of `inner` that the tile's first `inner`
    positions cannot see (tile_min: the tile's first position less the
    other operand's): blocks before it meet the whole tile, block
    index + t - 1 (t >= 1) only the tile from t * inner on.  -(-a // b)
    is ceil for either sign (NOT lax.div, which truncates toward zero)."""
    return -jnp.floor_divide(-tile_min, inner) + 1


def _walk_counts(q_off: int, kv_off: int, seq_q: int, seq_k: int,
                 block_q: int, block_k: int, window: Optional[int] = None):
    """(scores computed, blocks visited) by a kernel that tiles the
    queries, for static offsets: `_walk_blocks`' bounds in Python."""
    narrow = _narrow_steps(block_q, block_k)
    num_k = seq_k // block_k
    computed = visited = 0
    for qi in range(seq_q // block_q):
        row_min = q_off - kv_off + qi * block_q
        lo = 0 if window is None else min(
            max((row_min - (window - 1)) // block_k, 0), num_k)
        if narrow:
            j1 = -(-row_min // block_k) + 1
            whole = max(min(max(j1, 0), num_k) - lo, 0)
            steps = [t for t in range(1, narrow + 1)
                     if 0 <= j1 + t - 1 < num_k]
            computed += whole * block_q * block_k + sum(
                (block_q - t * block_k) * block_k for t in steps)
            visited += whole + len(steps)
        else:
            hi = (row_min + block_q - 1) // block_k + 1
            whole = max(min(max(hi, 0), num_k) - lo, 0)
            computed += whole * block_q * block_k
            visited += whole
    return computed, visited


def _dead_share(q_off: int, kv_off: int, seq_q: int, seq_k: int,
                block_q: int, block_k: int,
                window: Optional[int] = None) -> float:
    """Share of the scores a kernel that tiles the queries computes that
    no query may see (above the diagonal or behind the window), for
    static offsets, for the plan record."""
    computed, _ = _walk_counts(q_off, kv_off, seq_q, seq_k, block_q,
                               block_k, window)
    live = 0
    for row in range(seq_q):
        last = min(q_off - kv_off + row, seq_k - 1)
        first = 0 if window is None else max(
            q_off - kv_off + row - (window - 1), 0)
        live += max(last - first + 1, 0)
    return 1.0 - live / computed if computed else 0.0


def _query_minus_key(num_keys: int, num_queries: int):
    """[keys, queries] int32: index of the query less index of the key.
    Made once a program; a step's causal mask is a static slice of it
    compared with one scalar (first key position - first query position)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (num_keys, num_queries), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (num_keys, num_queries), 0))


_NOTHING_VISIBLE = 1 << 30      # a `first` no query index reaches


def _keep(visible, x, otherwise: float):
    """x where visible, else the constant.  lax.select, not jnp.where: a
    step's shapes are its own, so each jnp.where would be one more jit to
    trace in every process that builds the kernel."""
    return jax.lax.select(visible, x, jax.lax.full_like(x, otherwise))


def _dot(a, b, contract_a: int, contract_b: int):
    """MXU matmul in the operands' NATIVE dtype with f32 accumulation: a
    bf16×bf16 matmul runs the MXU at full rate, while upcasting inputs
    to f32 forces the multi-pass f32 path (~3-6× slower)."""
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32)


def _scaled(x, sm_scale: float):
    return (x.astype(jnp.float32) * sm_scale).astype(x.dtype)


def rope_reference(x, cos, sin):
    """Rotary embedding in XLA.  x: [b, s, heads, d]; cos, sin: [b, s, d/2]
    float32, gathered at the rows' positions.  Float32 arithmetic, rounded
    once to x's dtype."""
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _widen_rope(rope, heads: int = 1):
    """(cos, sin) [b, s, d/2] -> [b, s, heads x d] float32 as the kernels
    take them: for each of the heads a program works, cos twice and the
    sine with rotate_half's sign folded in, so that rope(x) = x * cos +
    swap_halves(x) * sin."""
    def twice(t, signs):      # a broadcast, where a concatenate would pad
        t = t.astype(jnp.float32)[:, :, None, :] * jnp.asarray(
            signs * heads, jnp.float32)[:, None]
        return t.reshape(*t.shape[:2], -1)

    cos, sin = rope
    return twice(cos, (1.0, 1.0)), twice(sin, (-1.0, 1.0))


def _swap_halves(x, heads: int = 1):
    """[rows, heads x d] with the two halves of each head's d exchanged
    (rotate_half without its sign, which `_widen_rope` folds into the
    sine), in VMEM: two lane slices a head and a concatenate, exact in any
    dtype.  Of the forms Mosaic takes on the v5e this one timed fastest
    (PERF.md, PR 33: a matmul with the d x d permutation needs the MXU's
    full-precision passes on the float32 sums and added 0.93 ms to a
    backward call at 160 x 2048 x 64, this 0.40; a lane roll of bfloat16
    is not implemented)."""
    half = x.shape[-1] // (2 * heads)
    return jnp.concatenate(
        [x[:, (i ^ 1) * half:((i ^ 1) + 1) * half]
         for i in range(2 * heads)], axis=1)


def _roped(x, cos, sin, heads: int = 1):
    """The rotary embedding of a [rows, heads x d] block, each head's d by
    itself, float32; cos, sin: the widened tables' rows.  With -sin it is
    the transpose, for a gradient."""
    return (x.astype(jnp.float32) * cos
            + _swap_halves(x, heads).astype(jnp.float32) * sin)


def _heads_a_program(d: int, d_v: int) -> int:
    """How many heads one program works: the operands cross HBM as their
    projections lay them, [b, s, heads x d], and a program's block of
    them is whole lane blocks of 128, so lcm(d, 128) / d heads (two of 64,
    one of 128), for the keys' width and the values' both."""
    return max(128 // math.gcd(d, 128), 128 // math.gcd(d_v, 128))


def _head_lanes(shape, j: int, heads: int):
    """[shape] bool: the lanes (last axis) of head j of `heads`."""
    d = shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return jnp.logical_and(lane >= j * d, lane < (j + 1) * d)


def _head_alone(x, j: int, heads: int, scale: float = 1.0):
    """[rows, heads x d] with head j's lanes times `scale` and every other
    lane zero, in x's dtype: against it a contraction over ALL the lanes
    is head j's over its own d, at the MXU passes the d-deep one costs (a
    contraction 64 deep half fills the array as it is) and with no lane
    shift.  The product is float32 rounded back: exact for 1 and for a
    power of two, `_scaled`'s rounding for another scale."""
    row = (1, x.shape[-1])
    factor = _keep(_head_lanes(row, j, heads),
                   jnp.full(row, scale, jnp.float32), 0.0)
    return (x.astype(jnp.float32) * factor).astype(x.dtype)


def _join_heads(per_head):
    """One [rows, heads x d] from an array a head, each right in its own
    head's lanes (what p_j . do or ds_j . q gives) and to be dropped in
    the others'."""
    out = per_head[-1]
    for j in range(len(per_head) - 2, -1, -1):
        out = jax.lax.select(_head_lanes(out.shape, j, len(per_head)),
                             per_head[j], out)
    return out


def _roped_from(nope: int, x, cos, sin):
    """[rows, nope + r] with its columns from nope on (lane-aligned) roped
    and rounded to x's dtype, the others as they are: latent attention's
    q, whose rotary part is its last r columns.  With -sin and a float32
    sum, that sum's gradient turned back, still float32."""
    return jnp.concatenate(
        [x[:, :nope], _roped(x[:, nope:], cos, sin).astype(x.dtype)], axis=1)


def _for_row_blocks(body, length: int, block: int) -> None:
    """body(rows) for each `block` rows of `length`, in a loop: one
    statement over a head's whole rows would hold them whole in VMEM,
    float32, between its load and its store."""
    from jax.experimental import pallas as pl

    def one(i, _):
        body(pl.ds(pl.multiple_of(i * block, block), block))
        return 0

    jax.lax.fori_loop(0, length // block, one, 0)


def _put(old, at: int, new):
    """`old` [.., queries] with its queries from `at` on (static) replaced."""
    return jnp.concatenate([old[:, :at], new], axis=1) if at else new


def _narrow_block(j, inner: int, num_blocks: int, first):
    """(start, first) of a block on the diagonal, which may lie outside
    the operand (a chunk from the past or the future): then an inside
    block is read and nothing of it is visible."""
    inside = jnp.logical_and(j >= 0, j < num_blocks)
    start = jnp.minimum(jnp.maximum(j, 0), num_blocks - 1) * inner
    return start, jax.lax.select(inside, first,
                                 jnp.int32(_NOTHING_VISIBLE))


def _visible(query_minus_key, first, window: Optional[int]):
    """The causal mask of a step, and under a window its trailing edge:
    a key `window` or more positions behind the query is out."""
    seen = query_minus_key >= first
    if window is not None:
        seen = jnp.logical_and(seen, query_minus_key < first + window)
    return seen


def _walk_blocks(step, carry, causal: bool, tile_min, tile: int, inner: int,
                 num_blocks: int, window: Optional[int] = None):
    """Run step(start, first, carry, lo) over the blocks of `inner` keys
    that a tile of queries meets (the forward's order: the loop,
    then the diagonal).  start: the block's first key; first: that key's
    position less the tile's first query's, for the mask; lo (static):
    where in the tile the queries that can see the block begin.  Under a
    window the loop starts at the block that holds the first key the
    tile's first query sees: the blocks behind it are not visited."""
    def whole(j, carry):
        return step(j * inner, j * inner - tile_min, carry, 0)

    if not causal:
        return jax.lax.fori_loop(0, num_blocks, whole, carry)
    narrow = _narrow_steps(tile, inner)
    lo = 0 if window is None else jnp.clip(
        jnp.floor_divide(tile_min - (window - 1), inner), 0, num_blocks)
    if not narrow:
        hi = jnp.floor_divide(tile_min + tile - 1, inner) + 1
        return jax.lax.fori_loop(lo, jnp.clip(hi, 0, num_blocks), whole,
                                 carry)
    j1 = _first_narrow_block(tile_min, inner)
    carry = jax.lax.fori_loop(lo, jnp.clip(j1, 0, num_blocks), whole, carry)
    for t in range(1, narrow + 1):
        j = j1 + (t - 1)
        carry = step(*_narrow_block(j, inner, num_blocks,
                                    j * inner - tile_min), carry, t * inner)
    return carry


# ---------------------------------------------------------------------------
# Pallas forward kernel (offset-aware, emits logsumexp)
# ---------------------------------------------------------------------------

def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, *refs,
                causal: bool, block_q: int, block_k: int, seq_k: int,
                sm_scale: float, fold_scale: bool, windowed: bool = False,
                rope_q0: Optional[int] = None, parts=None, heads: int = 1):
    """heads: how many heads' lanes the blocks hold beside each other
    (`_heads_a_program`; 1 builds the kernel of one): k and v are loaded
    and the output stored for all of them at once, every lane, and each
    head keeps its own scores, softmax state and accumulator.

    Several heads' refs end with one more scratch, [heads x d, keys]: their
    values turned once, at the heads' first query tile, so that a head's p .
    v reads its own ROWS of them as a plain operand.  (v^T . p on the
    loaded block gives all the heads' rows for each head's p: measured on
    the v5e at 5 x 2048 x 32 x 64, 1.84 ms a forward call against 1.62 so,
    PERF.md, PR 38.)

    rope_q0 (None: no rope, the kernel without): the row of the tables,
    which hold the KEYS' positions, at which the queries' begin.  Then refs
    holds the two tables before the outputs and, after them, a scratch for
    the heads' roped keys.

    parts (with rope_q0; None: whole operands, the kernel without): (nope,
    heads) of latent attention.  k_ref is then a head's [k_nope | v] as the
    projection lays it and v_ref the row's ONE rotary key, un-roped; the
    tables are as wide as that key, q is roped from column nope on, and a
    second scratch holds the row's roped rotary key, made once for all its
    heads."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    window = offs_ref[2] if windowed else None
    q = q_ref[0]  # [block_q, heads x d]
    d = v_ref.shape[-1] // heads  # the accumulator's rows: the values' width
    v_cols = slice(None)

    def q_table_rows():
        return pl.ds(pl.multiple_of(rope_q0 + qi * block_q,
                                    math.gcd(rope_q0, block_q)), block_q)

    if heads > 1:
        *refs, vt_ref = refs

        def turn_values(at):
            vt_ref[:, at] = v_ref[0, at, :].T

        @pl.when(qi == 0)
        def _():
            _for_row_blocks(turn_values, seq_k, block_k)

    if rope_q0 is None:
        o_ref, lse_ref = refs
    elif parts is None:
        cos_ref, sin_ref, o_ref, lse_ref, roped_k_ref = refs

        # A head's keys are roped once, at its first query tile, and stay
        # in scratch for the others (the axis runs in order, as the
        # backward's sum of dq needs it to).
        plain_k_ref, k_ref = k_ref, roped_k_ref

        @pl.when(qi == 0)
        def _():
            k_ref[0] = _roped(plain_k_ref[0], cos_ref[0], sin_ref[0],
                              heads).astype(k_ref.dtype)

        rows = q_table_rows()
        # rounded to the operand's dtype before the scale and any matmul,
        # as rope in XLA rounds it
        q = _roped(q, cos_ref[0, rows, :], sin_ref[0, rows, :],
                   heads).astype(q.dtype)
    else:
        nope, row_heads = parts
        cos_ref, sin_ref, o_ref, lse_ref, roped_k_ref, roped_pe_ref = refs
        # v is the lane-aligned end of the head's [k_nope | v]; the head's
        # keys, [k_nope | rope(k_pe)], are put together in scratch at its
        # first query tile, as the whole keys are roped there above
        plain_pe_ref, v_ref, k_ref = v_ref, k_ref, roped_k_ref
        d, v_cols = v_ref.shape[-1] - nope, slice(nope, None)

        def rope_pe(at):
            roped_pe_ref[at, :] = _roped(
                plain_pe_ref[0, at, :], cos_ref[0, at, :],
                sin_ref[0, at, :]).astype(roped_pe_ref.dtype)

        def put_keys(at):
            k_ref[0, at, :nope] = v_ref[0, at, :nope]
            k_ref[0, at, nope:] = roped_pe_ref[at, :]

        # the row's ONE rotary key is roped at the first of its heads
        @pl.when(jnp.logical_and(qi == 0,
                                 pl.program_id(0) % row_heads == 0))
        def _():
            _for_row_blocks(rope_pe, seq_k, block_k)

        @pl.when(qi == 0)
        def _():
            _for_row_blocks(put_keys, seq_k, block_k)

        rows = q_table_rows()
        q = _roped_from(nope, q, cos_ref[0, rows, :], sin_ref[0, rows, :])
    if heads > 1:       # a head's q alone in its lanes, the scale with it
        qs = [_head_alone(q, j, heads, sm_scale if fold_scale else 1.0)
              for j in range(heads)]
    else:
        qs = [_scaled(q, sm_scale) if fold_scale else q]
    query_minus_key = _query_minus_key(block_k, block_q) if causal else None

    def step(start, first, carry, lo: int):
        """The key block at `start` against queries [lo, block_q) of the
        tile, a head after the other.  Scores, statistics and the
        accumulator are held [keys | d, queries]; carry: (m, l, acc) of
        each head in turn."""
        visible, new = None, []
        for j, q in enumerate(qs):
            m, l, acc = (x[:, lo:] for x in carry[3 * j:3 * j + 3])
            if j == 0:      # one load of the block for all its heads
                start = pl.multiple_of(start, block_k)
                k_blk = k_ref[0, pl.ds(start, block_k), :]
                if heads == 1:
                    v_blk = v_ref[0, pl.ds(start, block_k), v_cols]
            s = _dot(k_blk, q[lo:], 1, 1)          # [block_k, block_q - lo]
            if not fold_scale:
                s = s * sm_scale
            if causal:
                if visible is None:     # and one mask
                    visible = _visible(query_minus_key[:, lo:], first, window)
                s = _keep(visible, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            # p in v's dtype for the second MXU matmul (f32 accumulation
            # preserved by preferred_element_type) — same as every
            # production flash kernel; probabilities are in [0, 1] so bf16
            # rounding here is benign relative to the softmax itself.
            acc = acc * alpha
            if heads > 1:       # this head's rows of the values, turned
                pv = _dot(vt_ref[j * d:(j + 1) * d, pl.ds(start, block_k)],
                          p.astype(vt_ref.dtype), 1, 0)
            else:
                pv = _dot(v_blk, p.astype(v_blk.dtype), 0, 0)
            new += [m_new, l_new, acc + pv]
        return tuple(_put(old, lo, x) for old, x in zip(carry, new))

    carry = (jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((d, block_q), jnp.float32)) * heads
    carry = _walk_blocks(
        step, carry, causal, offs_ref[0] - offs_ref[1] + qi * block_q,
        block_q, block_k, seq_k // block_k, window)
    outs, safe = [], []
    for m, l, acc in zip(carry[0::3], carry[1::3], carry[2::3]):
        l_safe = jnp.maximum(l, 1e-30)
        # Queries with no visible keys (possible in ring chunks "from the
        # future"): m stayed at -inf, so p accumulated exp(0)=1 garbage —
        # zero the output and mark lse = -inf ("no weight" for the merge).
        valid = m > _NEG_INF / 2
        outs.append(jnp.where(valid, acc / l_safe, 0.0))
        safe.append((valid, l_safe))
    # the heads' [d, queries] under each other, turned once: every lane of
    # the output's block is stored
    out = outs[0] if heads == 1 else jnp.concatenate(outs, axis=0)
    o_ref[0] = out.T.astype(o_ref.dtype)
    for j, (valid, l_safe) in enumerate(safe):
        m, l = carry[3 * j:3 * j + 2]
        lse = jnp.where(valid & (l > 0), m + jnp.log(l_safe), _NEG_INF)
        # lse is logically [block_q]; stored broadcast over an 8-sublane
        # axis so the block shape ends in (8, block_q) per Mosaic's tiling
        # constraint.
        lse_ref[j] = jnp.broadcast_to(lse, (8, block_q))


def _compiler_params(vmem_mib: int = 32):
    """Scoped VMEM above the v5e's default of 16 MiB: at sequence 8192
    and head size 128 the forward's resident K and V (double-buffered) and
    a [256, 2048] f32 score tile with its neighbours need 17.4 MiB; the
    backward, which holds a head's q, do, dq and dq's float32 sum whole,
    34.9 MiB inside the hybrid step program (of the chip's 128), so it
    asks for 48."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=vmem_mib << 20)


def _rope_operands(rope, programs: int, seq_k: int, d: int,
                   one_buffer: bool = False):
    """(operands, their BlockSpecs) of the widened tables [b, seq_k, d]: a
    row's whole table, whose block index does not move across the row's
    `programs` (its heads, or its groups of them) or their tiles, so the
    pipeline loads it once a row.  one_buffer: the table keeps ONE buffer
    (`_one_wide_head`: its second, which only a row's change would use, is
    8 MiB at 8192 x 256, 16 for the pair)."""
    from jax.experimental import pallas as pl

    if rope is None:
        return (), []
    spec = pl.BlockSpec(
        (1, seq_k, d), lambda g, i, offs: (g // programs, 0, 0),
        **({"pipeline_mode": pl.Buffered(1)} if one_buffer else {}))
    return tuple(rope), [spec, spec]


def _one_wide_head(heads: int, d: int) -> bool:
    """A program works ONE head wider than a lane block (a head of 256):
    what it holds whole is twice a head of 128's, and the chip's VMEM is
    what it was."""
    return heads == 1 and d > 128


def _lanes(width: int) -> int:
    """A row of `width` as VMEM holds it: whole lanes of 128."""
    return -(-width // 128) * 128


def _head_spec(rows: int, width: int, groups: int, whole: bool = False):
    """The BlockSpec that reads `rows` x `width` of a [b, t, h x w] array
    for program (g, i), g = row x groups + group: the group's lanes by
    lane-block index, rows i x rows on (whole: all t, whatever i)."""
    from jax.experimental import pallas as pl

    where = (lambda i: 0) if whole else (lambda i: i)
    return pl.BlockSpec(
        (1, rows, width),
        lambda g, i, offs: (g // groups, where(i), g % groups))


def _head_blocks(x, heads: int, groups: int, rows: Optional[int]):
    """(x [b, t, h, w] as its projection lays it, [b, t, h x w]: a reshape
    that XLA folds against the model's own; the BlockSpec that reads `rows`
    of it for program (g, i)): the lanes of the `heads` heads that program
    g works, no transpose and nothing padded.  rows=None: all t, whatever
    the tile."""
    b, t, h, w = x.shape
    return x.reshape(b, t, h * w), _head_spec(rows or t, heads * w, groups,
                                              rows is None)


def _latent_parts(q, kv, k_pe):
    """None for whole q, k, v.  Latent attention's parts are told by their
    ranks: where the values would be, [b, t, h, e], stands ONE rotary key
    for all heads, [b, t, r]; kv is then [b, t, h, nope + e] = [k_nope | v]
    and q [b, s, h, nope + r].  -> (nope, e, r)."""
    if k_pe.ndim != 3:
        return None
    r = k_pe.shape[-1]
    nope = q.shape[-1] - r
    return nope, kv.shape[-1] - nope, r


def _parts_operands(kv, k_pe, heads: int, rows: int):
    """(kv as its projection lays it, [b, t, h x (nope + e)], and k_pe;
    the BlockSpecs that read `rows` of them for program (g, i)): a head's
    columns of kv by lane-block index, no split and no transpose, and the
    row's one rotary key whatever the head.  rows=None: all t, whatever
    the tile (the forward, which holds a head's keys whole)."""
    from jax.experimental import pallas as pl

    b, t, _, width = kv.shape
    where = (lambda i: 0) if rows is None else (lambda i: i)
    return (kv.reshape(b, t, heads * width), k_pe), [
        pl.BlockSpec((1, rows or t, width),
                     lambda g, i, offs: (g // heads, where(i), g % heads)),
        pl.BlockSpec((1, rows or t, k_pe.shape[-1]),
                     lambda g, i, offs: (g // heads, where(i), 0))]


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _flash_fwd(q, k, v, offs, causal: bool, sm_scale: float,
               block_q: int, block_k: int,
               fold_scale: Optional[bool] = None,
               windowed: bool = False, rope=None):
    """fold_scale is for the tests alone (None: fold when exact).  rope:
    None or the widened tables (`_widen_rope`); the kernel then ropes q
    and k as it loads them.

    Jitted so that one trace serves both of a train step's calls (the
    primal under jax.checkpoint and the custom VJP's forward rule) and one
    lowering both of the program's copies (primal and remat): a kernel
    with straight-line steps is slow to trace, in every process."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, d_v = k.shape[1], v.shape[-1]
    if fold_scale is None:
        fold_scale = _scale_is_exact(sm_scale)
    parts = _latent_parts(q, k, v)
    if parts is None:
        # q, k, v and out cross HBM where the projections lay and read
        # them, [b, s, h x d]; a program works the `heads` heads of one
        # lane block (h is a multiple: `_chunk`), the grid's first axis
        # their groups within each row.
        heads = _heads_a_program(d, d_v)
        programs = h // heads
        qf, q_spec = _head_blocks(q, heads, programs, block_q)
        kv_operands, kv_specs = zip(_head_blocks(k, heads, programs, None),
                                    _head_blocks(v, heads, programs, None))
        out_shape = jax.ShapeDtypeStruct((b, sq, h * d_v), q.dtype)
        out_spec = _head_spec(block_q, heads * d_v, programs)
        table_width = heads * d
        scratch = [] if rope is None else [
            pltpu.VMEM((1, sk, table_width), k.dtype)]
        # The two float32 tables, a row's whole and double-buffered, are
        # four blocks of sk x 128 lanes beside the resident k and v and
        # the roped keys' scratch: 33.9 MiB needed at 8192 x 128, where
        # the 32 hold every shorter or rope-less call.
        table_mib = 0 if rope is None else -(
            -sk * _lanes(table_width) * 4 // 2 ** 20)
        # and whatever the heads' resident k and v, double-buffered, hold
        # beyond one head of 128 at 8192 rows (8 MiB): 12 more for two
        # heads of 192 / 128 there
        kv_mib = -(-sk * 4 * (_lanes(heads * d) + _lanes(heads * d_v))
                   // 2 ** 20)
        if heads > 1:       # the heads' values, turned
            scratch = scratch + [pltpu.VMEM((heads * d_v, sk), v.dtype)]
            kv_mib += -(-sk * 2 * _lanes(heads * d_v) // 2 ** 20)
        vmem_mib = max(32, 24 + 4 * table_mib) + max(kv_mib - 8, 0)
        wide = _one_wide_head(heads, d)
        if wide:
            # beside its tables and its resident k and v: the roped keys'
            # scratch, the q and out tiles and the [d, queries] accumulator,
            # by the lanes beyond 128.  XLA counts against a kernel's scoped
            # VMEM what it has itself placed there of the call's operands:
            # the call at 8192 x 256 needed 65.4 MiB inside a step program
            # of two rows and 77.1 inside the same program of one row (the
            # lse result and a table in VMEM) while its tables kept two
            # buffers; with one (`_rope_operands`) 16 less, of the 70 asked
            vmem_mib += -(-(2 * sk + 16 * block_q) * (_lanes(d) - 128)
                          // 2 ** 20)
    else:
        # q keeps its [bh, s, d] face (the benchmark's reader finds the
        # call by it) and out comes back [bh, s, e]; k_nope, v and the
        # rotary key are read where their projections left them.
        nope, d_v, table_width = parts
        heads, programs, wide = 1, h, False
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, offs: (bh, i, 0))
        out_shape = jax.ShapeDtypeStruct((b * h, sq, d_v), q.dtype)
        out_spec = pl.BlockSpec((1, block_q, d_v),
                                lambda bh, i, offs: (bh, i, 0))
        kv_operands, kv_specs = _parts_operands(k, v, h, None)
        scratch = [pltpu.VMEM((1, sk, d), k.dtype),
                   pltpu.VMEM((sk, table_width), k.dtype)]
        # What a program holds of the keys' rows, as VMEM holds them (lanes
        # of 128), beside the whole-operand call's 32 MiB: a head's
        # [k_nope | v], the rotary key and the two float32 tables, each
        # double-buffered, and the two scratches.  34 MiB at 8192 rows.
        vmem_mib = 32 + -(-sk * (4 * _lanes(nope + d_v) + 2 * _lanes(d)
                                 + 22 * _lanes(table_width)) // 2 ** 20)

    grid = (b * programs, sq // block_q)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        seq_k=sk, sm_scale=sm_scale, fold_scale=fold_scale,
        **({"windowed": True} if windowed else {}),
        **({} if rope is None else {"rope_q0": sk - sq}),
        **({} if parts is None else {"parts": (nope, h)}),
        **({} if heads == 1 else {"heads": heads}))
    tables, table_specs = _rope_operands(rope, programs, sk, table_width,
                                         wide)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[q_spec, *kv_specs, *table_specs],
            out_specs=[
                out_spec,
                # lse stays a row a head, [b x h, 8, sq]: a program's
                # heads are neighbours there
                pl.BlockSpec((heads, 8, block_q),
                             lambda g, i, offs: (g, 0, i)),
            ],
            scratch_shapes=scratch,
        ),
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct((b * h, 8, sq), jnp.float32),
        ],
        compiler_params=_compiler_params(vmem_mib),
        interpret=dispatch.interpret_mode(),
        name="flash_fwd",
    )(offs, qf, *kv_operands, *tables)
    # Named as the kernel wrote them, before the views below: what a
    # layer's remat keeps under save_only_these_names("attn_out",
    # "attn_lse") (models/common.maybe_remat) is then these two arrays,
    # and its backward holds no forward kernel.  A [b, s, h, 64] view kept
    # in their place would lie in half-filled lane blocks, twice the bytes.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse[:, 0, :], "attn_lse")     # [bh, sq]
    if parts is None:
        out = out.reshape(b, sq, h, d_v)
    else:
        out = out.reshape(b, h, sq, d_v).transpose(0, 2, 1, 3)
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernel: ONE pass recomputes the scores by block from the
# saved logsumexp and gives dq, dk and dv (the public flash-attention
# backward, with its two passes folded into the one that tiles the keys).
# corr = delta - dlse: -dlse comes in, the cotangent of the lse OUTPUT (zero
# for plain flash_attention, nonzero under ring attention's merge), and
# delta = rowsum(do * out) is made here from the output, one more input, at
# the first key tile.
#
# A program holds one key tile (k, v: [block_k, d]) and a head's WHOLE q,
# do, out, lse and corr in VMEM (of the heads that share its lane block:
# `_heads_a_program`), and meets the query blocks that can see the
# tile: the forward's plan with the roles turned (the diagonal cuts the
# tile's END off in straight-line steps, then the loop; under a window the
# loop ends early).  A step makes s, p, dp and ds once, [keys, queries],
# and five matmuls from them: s, dp, dv += p . do, dk += ds . q and the
# query block's dq = k^T . ds.  dk and dv are the program's own f32
# accumulators.  dq of the head's WHOLE query sequence is summed, held
# [d, sq], in a float32 scratch that lives across the key-tile axis: zeroed
# at the axis's first index, added to by every step (its block's columns),
# and at the last index turned, rounded once and stored to the dq output,
# whose block (the head's whole [sq, d]) does not move along that axis.
# One key tile (sk == block_k: `default_blocks` at sequences up to 2048, no
# window) or several (longer sequences, a window, passed blocks, ring
# chunks): the same sum in the same precision.
# With an exact scale k carries sm_scale into both s and dq, ds stays
# unscaled per score and the f32 accumulator of dk is scaled at the end.
# ---------------------------------------------------------------------------

def _bwd_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                minus_dlse_ref, *refs, causal: bool,
                block_q: int, block_k: int, seq_q: int, sm_scale: float,
                fold_scale: bool, windowed: bool = False,
                roped: bool = False, nope: Optional[int] = None,
                heads: int = 1):
    """heads: how many heads' lanes the blocks hold beside each other (1
    builds the kernel of one): q, k, v and do are loaded and dq, dk, dv
    stored for all of them at once; each head has its own scores, its own
    float32 sums of dk and dv (right in its own lanes, joined at the end)
    and its own rows of dq's sum.

    refs begins with the heads' output, one more input: delta = rowsum(do *
    out) is made here, a head's d at a time, at the heads' first key tile,
    and added to the -dlse that comes in, into the last scratch (in XLA it
    drew a float32 relayout of do * out after it: 168 MB a call at 5 x 2048
    x 32 x 64).

    roped: refs then holds the two tables (the KEYS' positions; the
    queries' are their last seq_q rows) before the outputs and, after the
    scratch, one more for the heads' roped q.  dq and dk are then the
    gradients of the UN-roped q and k: rope's transpose goes on the float32
    sums, before their one rounding.

    nope (with roped; None: whole operands, the kernel without): latent
    attention's parts.  k_ref is the tile of a head's [k_nope | v] as the
    projection lays it, v_ref that of the row's one rotary key, un-roped,
    do_ref a head's columns of the output's gradient; q is roped from
    column nope on; the head's output stands BEHIND the tables.  The
    outputs are dq, [dk_nope | dv] laid as k_ref is, and this head's share
    of the rotary key's gradient."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    window = offs_ref[2] if windowed else None
    k = k_ref[0]                      # [block_k, heads x d] native dtype
    v = v_ref[0]
    d = k.shape[-1] // heads
    if nope is not None:
        cos_ref, sin_ref, out_ref, dq_ref, dk_ref, dv_ref, kt_ref, dqt_ref, \
            roped_q_ref, corr_ref = refs
    elif roped:
        out_ref, cos_ref, sin_ref, dq_ref, dk_ref, dv_ref, kt_ref, dqt_ref, \
            roped_q_ref, corr_ref = refs
    if roped:
        k_rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        q_rows = slice(cos_ref.shape[1] - seq_q, cos_ref.shape[1])
    else:
        out_ref, dq_ref, dk_ref, dv_ref, kt_ref, dqt_ref, corr_ref = refs
    if nope is not None:
        # the tile's keys, [k_nope | rope(k_pe)], and its values
        k, v = jnp.concatenate(
            [k[:, :nope], _roped(v, cos_ref[0, k_rows, :], sin_ref[
                0, k_rows, :]).astype(k.dtype)], axis=1), k[:, nope:]
        d = k.shape[-1]
    elif roped:
        k = _roped(k, cos_ref[0, k_rows, :], sin_ref[0, k_rows, :],
                   heads).astype(k.dtype)
    k_s = _scaled(k, sm_scale) if fold_scale else k
    # k^T for dq, turned once a program into scratch: the steps' matmuls
    # read it as a plain operand (a transpose that feeds the MXU directly,
    # or a transposed-left matmul in the step, fails a check of the
    # compiler at a key tile of 2048).
    kt_ref[...] = k_s.T
    if heads > 1:
        # a head's k (the scale with it) and v alone in their lanes, for
        # its scores and dp; its rows of k^T and of dq's sum
        ks = [_head_alone(k, j, heads, sm_scale if fold_scale else 1.0)
              for j in range(heads)]
        vs = [_head_alone(v, j, heads) for j in range(heads)]
        head_rows = [slice(j * d, (j + 1) * d) for j in range(heads)]
    else:
        ks, vs, head_rows = [k_s], [v], [slice(None)]
    num_q = seq_q // block_q
    query_minus_key = _query_minus_key(block_k, block_q) if causal else None
    # this tile's first key less the queries' first position
    tile_min = offs_ref[1] - offs_ref[0] + ki * block_k

    def table_rows(at, sign: float):
        """(cos, sign * sin) at the rows `at` of q: latent attention's q
        is roped, and dq turned back, a block of rows at a time."""
        at = pl.ds(pl.multiple_of(at.start + q_rows.start, math.gcd(
            q_rows.start, block_q)), block_q)
        return cos_ref[0, at, :], sign * sin_ref[0, at, :]

    @pl.when(ki == 0)
    def _():
        dqt_ref[...] = jnp.zeros(dqt_ref.shape, dqt_ref.dtype)

        def make_corr(at):
            # delta - dlse, delta turned so that its rows lie along the
            # lanes as lse's do
            do_out = (do_ref[0, at, :].astype(jnp.float32)
                      * out_ref[0, at, :].astype(jnp.float32)).T
            e = do_out.shape[0] // heads
            for j in range(heads):
                own = do_out if heads == 1 else do_out[j * e:(j + 1) * e]
                corr_ref[j, :, at] = jnp.sum(
                    own, axis=0, keepdims=True) + minus_dlse_ref[j, :, at]

        if nope is not None:
            def rope_q_and_make_corr(at):
                roped_q_ref[0, at, :] = _roped_from(
                    nope, q_ref[0, at, :], *table_rows(at, 1.0))
                make_corr(at)

            return _for_row_blocks(rope_q_and_make_corr, seq_q, block_q)
        if roped:       # the heads' q, roped once for all their key tiles
            roped_q_ref[0] = _roped(
                q_ref[0], cos_ref[0, q_rows, :], sin_ref[0, q_rows, :],
                heads).astype(roped_q_ref.dtype)
        _for_row_blocks(make_corr, seq_q, block_q)

    q_ref = roped_q_ref if roped else q_ref     # what the steps read

    def step(start, first, carry, hi: int):
        """The query block at `start` against keys [0, hi) of the tile (the
        roles turn: the diagonal cuts the tile's END off), a head after
        the other.  Scores are held [keys, queries], as in the forward;
        carry: (dk, dv) of each head in turn."""
        visible, new = None, []
        for j, (k_j, v_j, at) in enumerate(zip(ks, vs, head_rows)):
            dk, dv = carry[2 * j:2 * j + 2]
            if j == 0:      # one load of the blocks for all their heads
                start = pl.multiple_of(start, block_q)
                rows = pl.ds(start, block_q)
                q_blk = q_ref[0, rows, :]
                do_blk = do_ref[0, rows, :]
            lse_blk = lse_ref[j, 0:1, rows]                 # [1, block_q]
            corr = corr_ref[j, 0:1, rows]
            s = _dot(k_j[:hi], q_blk, 1, 1)                 # [hi, block_q]
            if not fold_scale:
                s = s * sm_scale
            p = jnp.exp(s - lse_blk)
            if causal:
                if visible is None:     # and one mask
                    visible = _visible(query_minus_key[:hi], first, window)
                p = _keep(visible, p, 0.0)
            dv_new = dv[:hi] + _dot(p.astype(do_blk.dtype), do_blk, 1, 0)
            ds = p * (_dot(v_j[:hi], do_blk, 1, 1) - corr)  # dp^T = v · do^T
            if not fold_scale:
                ds = ds * sm_scale
            ds = ds.astype(q_blk.dtype)
            dk_new = dk[:hi] + _dot(ds, q_blk, 1, 0)
            # dq^T += k^T · ds, [d, block_q] (a step whose block lies
            # outside the operand reads an inside block and adds exact
            # zeros to it).
            dqt_ref[at, rows] += _dot(kt_ref[at, :hi], ds, 1, 0)
            if hi < block_k:
                dk_new = jnp.concatenate([dk_new, dk[hi:]], axis=0)
                dv_new = jnp.concatenate([dv_new, dv[hi:]], axis=0)
            new += [dk_new, dv_new]
        return tuple(new)

    def whole(j, carry):
        return step(j * block_q, tile_min - j * block_q, carry, block_k)

    carry = (jnp.zeros((block_k, k.shape[-1]), jnp.float32),
             jnp.zeros((block_k, v.shape[-1]), jnp.float32)) * heads
    if causal:
        # First query block whose last row sees this tile's first key,
        # then one block for each further `block_q` keys of the tile.
        first_blk = jnp.floor_divide(tile_min, block_q)
        narrow = _narrow_steps(block_k, block_q)
        for t in range(narrow):
            j = first_blk + t
            carry = step(*_narrow_block(j, block_q, num_q,
                                        tile_min - j * block_q),
                         carry, (t + 1) * block_q)
        # Under a window the loop ends with the block that holds the last
        # query to see the tile's last key.
        end = num_q if window is None else jnp.clip(jnp.floor_divide(
            tile_min + block_k + window - 2, block_q) + 1, 0, num_q)
        carry = jax.lax.fori_loop(
            jnp.clip(first_blk + narrow, 0, num_q), end, whole, carry)
    else:
        carry = jax.lax.fori_loop(0, num_q, whole, carry)
    # each head's sums are right in its own lanes: all lanes are stored
    dk, dv = _join_heads(carry[0::2]), _join_heads(carry[1::2])
    if fold_scale:
        dk = dk * sm_scale
    if nope is not None:
        # dk_ref: [dk_nope | dv] beside each other, as the projection's
        # gradient reads them; dv_ref: this head's gradient of the one
        # rotary key, rope turned back on the float32 sum
        dk, dv = jnp.concatenate([dk[:, :nope], dv], axis=1), _roped(
            dk[:, nope:], cos_ref[0, k_rows, :], -sin_ref[0, k_rows, :])
    elif roped:
        dk = _roped(dk, cos_ref[0, k_rows, :], -sin_ref[0, k_rows, :], heads)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        if nope is not None:
            def turn_dq(at):
                dq_ref[0, at, :] = _roped_from(
                    nope, dqt_ref[:, at].T, *table_rows(at, -1.0)).astype(
                        dq_ref.dtype)

            return _for_row_blocks(turn_dq, seq_q, block_q)
        dq = dqt_ref[...].T
        if roped:
            dq = _roped(dq, cos_ref[0, q_rows, :], -sin_ref[0, q_rows, :],
                        heads)
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _lse8(x, bh, s):
    """[bh, s] f32 -> [bh, 8, s] sublane-broadcast (Mosaic tiling)."""
    return jnp.broadcast_to(x[:, None, :], (bh, 8, s))


def _flash_bwd(q, k, v, out, lse, offs, dout, dlse, causal, sm_scale,
               blocks, windowed=False, rope=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, d_v = k.shape[1], v.shape[-1]
    bh = b * h
    block_q, block_k = blocks
    parts = _latent_parts(q, k, v)
    if parts is None:
        # q, k, v, do and dq, dk, dv where the projections lay and their
        # gradients read them, [b, s, h x d], `heads` heads a program
        # (`_flash_fwd`); do and v (and dv) in the values' width.
        heads = _heads_a_program(d, d_v)
        programs = h // heads
        qf, full_q = _head_blocks(q, heads, programs, None)
        kf, k_tile = _head_blocks(k, heads, programs, block_k)
        vf, v_tile = _head_blocks(v, heads, programs, block_k)
        dof, full_do = _head_blocks(dout, heads, programs, None)
        outf, full_out = _head_blocks(out, heads, programs, None)
        table_width = heads * d
        dq_shape = jax.ShapeDtypeStruct(qf.shape, q.dtype)
        grads = [jax.ShapeDtypeStruct(kf.shape, k.dtype),
                 jax.ShapeDtypeStruct(vf.shape, v.dtype)]
        grad_specs = [k_tile, v_tile]
    else:
        # Everything but q and dq where XLA lays it: a head's [k_nope | v]
        # and [dk_nope | dv] in the projection's columns, do in the output
        # projection's, the one rotary key's gradient a share a head.
        nope, d_v, table_width = parts
        heads, programs = 1, h
        qf = q.transpose(0, 2, 1, 3).reshape(bh, sq, d)
        full_q = pl.BlockSpec((1, sq, d), lambda g, i, offs: (g, 0, 0))
        dq_shape = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
        (kf, vf), (k_tile, v_tile) = _parts_operands(k, v, h, block_k)
        # do as the output projection's gradient lays it, and out as the
        # forward gave it (turning back what `_flash_fwd` turned: XLA
        # folds the two, where a reshape of the turned out to [b, s, h x e]
        # is a pass of its own, the tiles of the two not being the same)
        dof = dout.reshape(b, sq, h * d_v)
        outf = out.transpose(0, 2, 1, 3).reshape(bh, sq, d_v)
        full_do = pl.BlockSpec((1, sq, d_v),
                               lambda g, i, offs: (g // h, 0, g % h))
        full_out = pl.BlockSpec((1, sq, d_v), lambda g, i, offs: (g, 0, 0))
        grads = [jax.ShapeDtypeStruct(kf.shape, k.dtype),
                 jax.ShapeDtypeStruct((bh, sk, table_width), v.dtype)]
        grad_specs = [k_tile, pl.BlockSpec((1, block_k, table_width),
                                           lambda g, i, offs: (g, i, 0))]
    lse8 = _lse8(lse, bh, sq)
    # (delta + (-dlse)) enters every key of a query uniformly, one term:
    # delta = rowsum(do * out) is made in the kernel and added to this
    minus_dlse8 = _lse8(0.0 - dlse.astype(jnp.float32), bh, sq)

    seq_spec = pl.BlockSpec((heads, 8, sq), lambda g, i, offs: (g, 0, 0))
    tables, table_specs = _rope_operands(
        rope, programs, sk, table_width,
        parts is None and _one_wide_head(heads, d))
    if parts is None:       # the tables come last, the parts' output
        tables, table_specs = (outf, *tables), [full_out, *table_specs]
    else:
        tables, table_specs = (*tables, outf), [*table_specs, full_out]
    # A float32 table block as VMEM holds it (128 lanes): two tables, each
    # double-buffered, the roped q and the float32 dq being roped come to
    # under six of them (58.0 MiB needed at 8192 x 128; at 2048 x 64 the
    # 48 hold).
    table_mib = -(-sk * max(heads * d, 128) * 4 // 2 ** 20)
    # What a program holds of its heads WHOLE, as VMEM holds it (lanes of
    # 128): q and dq double-buffered, do double-buffered, dq's float32 sum.
    # 16 MiB at 8192 x 128, inside the 48; keys of 192 need 26.
    lanes, v_lanes = _lanes(heads * d), _lanes(heads * d_v)
    head_mib = -(-sq * (8 * lanes + 4 * v_lanes + 4 * heads * d) // 2 ** 20)
    if parts is not None:
        # beside the head's whole rows its roped q, its output (double-
        # buffered) and the two tables' four buffers (lanes of 128): 90
        # MiB at 8192 rows, where the cell's step program compiled at 80
        # and not at 70 before the output came in
        vmem_mib = 40 + head_mib + -(-(sq * (2 * lanes + 4 * v_lanes)
                                       + 16 * sk * _lanes(table_width))
                                     // 2 ** 20)
    else:
        # the heads' output, double-buffered, beside what stood before it
        # came in: 4 MiB at 8192 x 128
        out_mib = -(-sq * 4 * v_lanes // 2 ** 20)
        # and what a program holds a HEAD of as wide as all its lanes:
        # the float32 sums of dk and dv, twice across a step, and the k
        # and v with the other heads' lanes zero; beyond the one head of
        # 128 that always fitted, 6 MiB more for two of 64 at a key tile
        # of 2048 and 24 for two of 192 (103.7 MiB needed at 8192 rows)
        sums_mib = -(-heads * block_k * 12 * (lanes + v_lanes) // 2 ** 20) - 6
        vmem_mib = out_mib + max(sums_mib, 0) + (
            32 + head_mib if rope is None else 40 + 6 * table_mib)
        vmem_mib = max(48, vmem_mib)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, seq_q=sq, sm_scale=sm_scale,
                          fold_scale=_scale_is_exact(sm_scale),
                          **({"windowed": True} if windowed else {}),
                          **({} if rope is None else {"roped": True}),
                          **({} if parts is None else {"nope": nope}),
                          **({} if heads == 1 else {"heads": heads})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * programs, sk // block_k),
            in_specs=[full_q, k_tile, v_tile, full_do, seq_spec, seq_spec,
                      *table_specs],
            out_specs=[full_q, *grad_specs],
            scratch_shapes=[pltpu.VMEM((heads * d, block_k), k.dtype),
                            pltpu.VMEM((heads * d, sq), jnp.float32)]
            + ([] if rope is None else [pltpu.VMEM((1, sq, heads * d),
                                                   q.dtype)])
            + [pltpu.VMEM((heads, 8, sq), jnp.float32)],
        ),
        out_shape=[dq_shape, *grads],
        compiler_params=_compiler_params(vmem_mib),
        interpret=dispatch.interpret_mode(),
        name="flash_bwd",
    )(offs, qf, kf, vf, dof, lse8, minus_dlse8, *tables)

    if parts is None:
        return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)
    # dv here is the rotary key's gradient, a share a head
    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return dq, dk.reshape(k.shape), jnp.sum(
        dv.reshape(b, h, sk, table_width), axis=1,
        dtype=jnp.float32).astype(v.dtype)


# ---------------------------------------------------------------------------
# custom VJP over (out, lse).  `blocks` is the (block_q, block_k) of the
# forward and of the backward, in that order, then the window (or None).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_lse(q, k, v, offs, rope, causal, sm_scale, blocks):
    return _flash_fwd(q, k, v, offs, causal, sm_scale, *blocks[0], None,
                      blocks[2] is not None, rope)


def _flash_lse_fwd(q, k, v, offs, rope, causal, sm_scale, blocks):
    out, lse = _flash_fwd(q, k, v, offs, causal, sm_scale, *blocks[0], None,
                          blocks[2] is not None, rope)
    # Named residuals, for a remat policy that keeps them ("dots_no_mlp");
    # out and lse carry their names from `_flash_fwd`, and are the primal
    # outputs too: W_o's gradient reads the kept out, not a second forward.
    q_r = checkpoint_name(q, "attn_q")
    k_r = checkpoint_name(k, "attn_k")
    v_r = checkpoint_name(v, "attn_v")
    return (out, lse), (q_r, k_r, v_r, out, lse, offs, rope)


def _flash_lse_bwd(causal, sm_scale, blocks, res, cts):
    q, k, v, out, lse, offs, rope = res
    dout, dlse = cts
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, offs, dout, dlse,
                            causal, sm_scale, blocks[1],
                            blocks[2] is not None, rope)
    # offs (int positions) has no gradient, the tables are constants
    return dq, dk, dv, None, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def _record_plan(q_off, kv_off, causal: bool, sm_scale: float,
                 seq_q: int, seq_k: int, blocks, roped: bool,
                 widths=None, rope_width: Optional[int] = None,
                 heads=None) -> None:
    """Say in `dispatch.taken()` what the kernels were built to do: each
    kernel's (block_q x block_k), that dq comes out of the backward's one
    pass and over how many key tiles it is summed there, whether the scale
    left the score tile, and what share of each kernel's computed scores
    no query may see (known here only when the offsets are static; a
    traced offset decides it at run time); under a window also the window
    and the share of the forward's (tile, block) pairs that it visits;
    `rope_in_kernel` when the kernels rope q and k themselves; `widths`
    (keys', values') where the two differ, as `dqk192,dv128`; `rope_width`
    where the operands are latent attention's parts, whose last columns
    the kernels rope, as `latent_parts,rope_in_kernel64of192`; `heads` =
    (heads a program works, the keys' width) where the operands are whole
    q, k, v, taken as their projections lay them, as
    `operands_bshd,heads2x64`."""
    (fq, fk), (kv_q, kv_k), window = blocks
    static = isinstance(q_off, int) and isinstance(kv_off, int)
    if not causal:
        dead = "dead0%"
    elif static:
        # the backward tiles the keys: the same walk with the sequences
        # reversed
        shares = (_dead_share(q_off, kv_off, seq_q, seq_k, fq, fk, window),
                  _dead_share(1 - kv_off - seq_k, 1 - q_off - seq_q,
                              seq_k, seq_q, kv_k, kv_q, window))
        dead = "dead" + "/".join("%.0f" % (100 * x) for x in shares) + "%"
    else:
        dead = "dead_by_offset"
    scale = "scale_folded" if _scale_is_exact(sm_scale) else "scale_per_score"
    plan = f"fwd{fq}x{fk},bwd{kv_q}x{kv_k},dq_in_pass"
    if seq_k // kv_k > 1:
        plan += f",dq_over{seq_k // kv_k}tiles"
    plan += f",{scale},{dead}"
    if window is not None:
        plan += f",window{window}"
        if static:
            _, visited = _walk_counts(q_off, kv_off, seq_q, seq_k, fq, fk,
                                      window)
            plan += ",visited%.1f%%" % (
                100.0 * visited / ((seq_q // fq) * (seq_k // fk)))
    if roped:
        plan += ",rope_in_kernel"
    if widths is not None:
        plan += ",dqk%d,dv%d" % widths
    if rope_width is not None:
        plan += ",latent_parts,rope_in_kernel%dof%d" % (rope_width, widths[0])
    if heads is not None:
        plan += ",operands_bshd,heads%dx%d" % heads
    dispatch.record("flash_attention.plan", plan)


def _chunk(q, k, v, q_off, kv_off, causal, sm_scale, blocks, window=None,
           rope=None):
    """rope: None, or (cos, sin) [b, seq_k, d/2] at the KEYS' positions (the
    queries' are the last seq_q rows): attention over rope(q), rope(k), the
    kernels roping the tiles they load.  Or latent attention's parts
    (`_latent_parts`): k = [k_nope | v] by head, v = the one rotary key,
    the tables as wide as that key."""
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    blocks = (*blocks, window)
    parts = _latent_parts(q, k, v)
    d, e = q.shape[-1], v.shape[-1] if parts is None else parts[1]
    heads = _heads_a_program(d, e) if parts is None else 1
    _record_plan(q_off, kv_off, causal, sm_scale, q.shape[1], k.shape[1],
                 blocks, rope is not None and parts is None,
                 None if e == d else (d, e),
                 None if parts is None else parts[2],
                 (heads, d) if parts is None else None)
    if rope is not None:
        rope = _widen_rope(rope, heads)
    # Under a window the scalars are [q_off, kv_off, window]: the kernels
    # read the window there, and a windowed call shows in a trace by its
    # first operand, s32[3] (the benchmark's swa reader finds it so).
    offs = jnp.stack([jnp.asarray(x, jnp.int32) for x in
                      (q_off, kv_off) + (() if window is None else (window,))])
    b, sq, h = q.shape[:3]
    spare = -h % heads
    if not spare:
        return _flash_lse(q, k, v, offs, rope, causal, sm_scale, blocks)
    # A head count that does not fill its last lane block (25 heads of 64)
    # goes in with zero heads behind it, whose output and gradients are
    # dropped: a copy in XLA, and the one layout still.
    out, lse = _flash_lse(
        *(jnp.pad(x, ((0, 0), (0, 0), (0, spare), (0, 0))) for x in (q, k, v)),
        offs, rope, causal, sm_scale, blocks)
    return out[:, :, :h], lse.reshape(b, h + spare, sq)[:, :h].reshape(
        b * h, sq)


def flash_attention_chunk(q, k, v, q_off, kv_off, causal: bool = True,
                          sm_scale: Optional[float] = None,
                          block_q: int = 128, block_k: int = 128):
    """Offset-aware flash attention returning (out, lse).

    q_off / kv_off: GLOBAL position of q[:,0] / k[:,0] (may be traced —
    ring attention passes per-device values).  lse is [b*h, sq] float32;
    rows with no visible keys get lse = -inf (merge-neutral).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return _chunk(q, k, v, q_off, kv_off, causal, sm_scale,
                  ((block_q, block_k),) * 2)


def _blocks(d: int, sq: int, sk: int, dtype, block_q, block_k, window=None):
    """`default_blocks`' plan unless the caller passes a block size, which
    then holds for both kernels."""
    if block_q is None and block_k is None:
        return default_blocks(d, sq, sk, dtype, window)
    return ((min(block_q or 512, sq), min(block_k or 512, sk)),) * 2


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None, rope=None):
    """Tiled attention. q:[b,s,h,d], k:[b,t,h,d], v:[b,t,h,e] -> [b,s,h,e].
    The values may have another width than the keys (e != d: latent
    attention's 192 / 128): the kernels then contract the scores over d and
    carry e through the output, do and dv, with nothing padded, and the
    plan says `dqk<d>,dv<e>`; e == d builds exactly the kernels without.

    rope=(cos, sin): attention over rope(q), rope(k), for q and k as they
    come from their projections.  The tables are float32 [b, t, d/2],
    gathered at the KEYS' positions (`cos[positions]`); the queries take
    their last s rows (ends aligned, as the causal mask is).  The Pallas
    kernels, on the TPU and interpreted, rope the q and k tiles as they
    load them (float32, rounded to the operands' dtype before the scale
    and any matmul, which is where rope in XLA rounds) and apply rope's
    transpose to their float32 sums of dq and dk before those are rounded,
    so that q and k cross HBM once, un-roped, and the gradients with
    respect to them come out of the one call; the plan then says
    `rope_in_kernel`.  The XLA fallback, and a call whose queries begin at
    a row of the tables that is no multiple of 8, rope with
    `rope_reference` first and go on as without.  rope=None (a model
    without rotary positions, or one that ropes elsewhere) builds exactly
    the kernels without.

    window (causal only): query t sees keys s with 0 <= t - s < window.
    The kernels do not visit the blocks wholly behind a tile's window
    and mask the trailing edge in the blocks they do; window=None builds
    exactly the causal kernels.

    Uses the Pallas kernels on TPU (or in interpret mode for tests); falls
    back to the jnp reference elsewhere.  Heads must already be expanded
    (GQA repeat happens in the model).  When sq < sk the windows are
    end-aligned (decode convention), matching attention_reference.

    The kernels follow one plan made from what they can see (the comment
    above `_scale_is_exact`): a program's tile meets the blocks under the
    diagonal whole and the blocks on it only with the part that can see
    them; scores are held [keys, queries] where softmax reduces; a
    power-of-two sm_scale (head size 64: 0.125) is applied to the [tile,
    d] operand and the accumulators instead of every score, which is
    exact.  Block sizes the caller does not pass come from
    `default_blocks(head_dim, sq, sk, dtype)`, each kernel its own; a
    block_q / block_k that is passed holds for both kernels.
    `dispatch.taken()` holds the plan under "flash_attention.plan".

    Under an ambient multi-device mesh (jax.sharding.set_mesh) the kernel
    runs per shard inside a shard_map — batch over the data/fsdp axes,
    heads over the tensor axis (parallel/sharding.DEFAULT_RULES): GSPMD
    cannot partition a Mosaic kernel itself.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1]
    if window is not None and window >= sk:
        window = None          # every key a query may see is in its window
    blocks = _blocks(d, sq, sk, q.dtype, block_q, block_k, window)
    pallas = all(_can_use_pallas(sq, sk, d, bq, bk, v.shape[-1])
                 for bq, bk in blocks)
    # The kernels read the queries' rows of the tables at (sk - sq) on:
    # float32 rows come in sublanes of 8.
    if rope is not None and not (pallas and (sk - sq) % 8 == 0):
        cos, sin = rope
        q = rope_reference(q, cos[:, sk - sq:], sin[:, sk - sq:])
        k = rope_reference(k, cos, sin)
        rope = None
    if not pallas:
        dispatch.record("flash_attention", "xla")
        return attention_reference(q, k, v, causal, sm_scale, window)
    dispatch.record("flash_attention", "interpret"
                    if dispatch.interpret_mode() else "pallas")
    tables = () if rope is None else tuple(rope)

    def kernel(q, k, v, *tables):
        return _chunk(q, k, v, sk - sq, 0, causal, sm_scale, blocks,
                      window, tables or None)[0]

    return _per_shard(kernel, q, k, v, *tables)


def _per_shard(kernel, *operands):
    """kernel(*operands), under an ambient multi-device mesh per shard
    inside a shard_map: batch over data / fsdp and heads, the third axis of
    the 4-D operands (the first is q), over tensor; the 3-D ones (rope's
    tables, latent attention's one rotary key) have no heads and are whole
    over tensor."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return kernel(*operands)
    from jax.sharding import PartitionSpec as P

    # An axis shards a dim only where it divides it; otherwise that dim
    # is computed replicated (small eval batches on a wide mesh).
    q = operands[0]
    sizes = dict(mesh.shape)
    batch = tuple(a for a in ("data", "fsdp") if a in sizes)
    if q.shape[0] % math.prod(sizes[a] for a in batch):
        batch = ()
    heads = "tensor" if ("tensor" in sizes
                         and q.shape[2] % sizes["tensor"] == 0) else None
    spec = P(batch or None, None, heads, None)
    table_spec = P(batch or None, None, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=tuple(spec if x.ndim == 4 else table_spec
                       for x in operands),
        out_specs=spec, check_vma=False)(*operands)


def latent_flash_attention(q, kv, k_pe, rope, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None):
    """Latent attention from its PARTS, as their projections lay them:

      q     [b, s, h, nope + r]  un-roped, its last r columns rotary;
      kv    [b, t, h, nope + e]  a head's [k_nope | v];
      k_pe  [b, t, r]            ONE rotary key, shared by all heads,
                                 un-roped;
      rope  (cos, sin) float32 [b, t, r/2] at the KEYS' positions, the
            queries' their last s rows: `flash_attention`'s, r wide.

    -> [b, s, h, e]: `flash_attention` over q' = [q_nope | rope(q_pe)],
    k' = [k_nope | rope(k_pe)] for every head, and v, at sm_scale
    (default (nope + r)^-0.5), rope pairing column i of the r with column i
    + r/2 (`rope_reference`; a model that pairs otherwise reorders the
    rotary columns of its two projections at use: q_pe . k_pe does not see
    one permutation of both).

    The Pallas kernels, on the TPU and interpreted, never see q', k' or v
    in HBM: they read a head's [k_nope | v] from kv by lane-block index
    (nope and e multiples of 128 on the TPU), the one rotary key whatever
    the head, rope it and the q tile's last r columns in VMEM (float32,
    rounded to the operands' dtype before any matmul, as rope in XLA
    rounds) and put the head's keys together there.  The backward writes
    [dk_nope | dv] laid as kv is, dq with rope turned back on its float32
    sum, and the rotary key's gradient a share a head, which XLA sums;
    `do` is read from [b, s, h x e] as the output projection's gradient
    lays it.  q goes in as [b x h, s, nope + r] and the output comes back
    [b x h, s, e] (the forward's face, which the benchmark's reader finds:
    one relayout each in XLA), as does dq.  The plan says
    `dqk<nope + r>,dv<e>,latent_parts,rope_in_kernel<r>of<nope + r>`.

    Where the kernels cannot run so (off the TPU, widths off the lanes,
    queries that begin at a row of the tables that is no multiple of 8),
    q', k' and v are put together here and `flash_attention` takes them."""
    b, sq, h, d = q.shape
    sk, r = k_pe.shape[1], k_pe.shape[-1]
    nope = d - r
    e = kv.shape[-1] - nope
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    blocks = _blocks(d, sq, sk, q.dtype, block_q, block_k)
    on_lanes = dispatch.interpret_mode() or (nope % 128 == 0 and e % 128 == 0)
    if not (on_lanes and (sk - sq) % 8 == 0
            and all(_can_use_pallas(sq, sk, d, bq, bk, e)
                    for bq, bk in blocks)):
        cos, sin = rope
        q = jnp.concatenate([q[..., :nope], rope_reference(
            q[..., nope:], cos[:, sk - sq:], sin[:, sk - sq:])], axis=-1)
        k_pe = rope_reference(k_pe[:, :, None, :], cos, sin)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, sk, h, r))], axis=-1)
        return flash_attention(q, k, kv[..., nope:], causal, sm_scale,
                               block_q, block_k)
    dispatch.record("flash_attention", "interpret"
                    if dispatch.interpret_mode() else "pallas")

    def kernel(q, kv, k_pe, *tables):
        return _chunk(q, kv, k_pe, sk - sq, 0, causal, sm_scale, blocks,
                      None, tables)[0]

    return _per_shard(kernel, q, kv, k_pe, *rope)
