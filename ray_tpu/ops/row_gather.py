"""Gather rows by index and sum them: the rows that are named, not the slots.

What a routed-expert layer needs to carry rows between token order and
expert order (models/moe.py's `_combine`, `_place` and their transposes):

    gather_sum(table [n, h], rows [out_rows * cap] int32,
               counts [out_rows] int32, weights [out_rows * cap] f32 or None)
        -> [out_rows, h] in table's dtype:
    out[i] = sum over j < counts[i], in that order, of
             weights[i * cap + j] x table[rows[i * cap + j]]
    (float32 sum; a row with no entry is zero; the slots behind a row's
    count are never read as indices)

An out row has `cap` slots and names its entries in the first counts[i] of
them.  XLA's formulation is `cap` gathers of out_rows rows and a select: it
costs by the slots.  The kernel costs by the entries: a grid over tiles of
out rows; a tile's index lists come to SMEM as blocks; the scalar core lists
the tile's entries, then one loop starts a copy for entry e + AHEAD and adds
entry e, so the copies are in flight while the vector unit sums.

A copy moves the aligned STRIP of 8 rows that holds the named row (Mosaic
slices a tiled HBM array only by whole tiles of 8 rows; the strip is
contiguous there); the sum reads the ONE row out of the strip in VMEM (a
32-bit sublane: a bfloat16 table's rows lie two to a sublane and a row's
float32 value is its 16 bits moved high) and adds it to the out row's row
of a float32 accumulator of the tile.  The strip-mates of a named row are
never read, so they may hold anything.  The body is a few whole-row
operations in three short loops: it is traced and lowered at every start
of a program that holds it (twelve times in the linear-attention cell's).

What the chip said (v5e, PR 45, `PERF.md`): a copy costs 108-135 ns to start
and wait for, whatever its bytes; XLA's gather 24-40 ns a slot.  So the
kernel is for lists mostly empty (cap > 1: a token's k assignments of which
a chip holds a few), and at cap = 1, where a slot is a row, the XLA gather
stays (`_use_pallas`).

  - Pallas on the TPU where cap > 1, interpreted where
    RAY_TPU_PALLAS_INTERPRET=1 asks (any cap); elsewhere `_xla_gather_sum`,
    which also defines the semantics in the tests.
  - `path(...)` says which a call would take; the caller records it
    (`dispatch.taken()["routed_experts"]`, models/moe.py).
  - Not differentiable: its callers are the bodies of custom VJPs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch

STRIP = 8           # rows a copy moves: the HBM tile's rows
TILE_ROWS = 128     # out rows a grid step, their float32 sums in VMEM
META_ROWS = 1024    # out rows an SMEM block of indices covers (1-D blocks
#                     come in multiples of 1024 elements)
RING = 32           # strips in VMEM
AHEAD = 16          # copies in flight before the first is waited for


# ---------------------------------------------------------------------------
# XLA formulation: the semantics, and the path off the TPU and at cap = 1
# ---------------------------------------------------------------------------

def _xla_gather_sum(table, rows, counts, weights):
    """A gather a slot: one gather of [out_rows, cap] rows would be re-laid
    for its cap-long axis before the sum.  A select, not a product: a slot
    behind the count names nothing."""
    out_rows = counts.shape[0]
    rows = jnp.clip(rows, 0, table.shape[0] - 1).reshape(out_rows, -1)
    cap = rows.shape[1]
    if cap == 1 and weights is None:        # one gather, nothing to sum
        return jnp.where(counts[:, None] > 0, table[rows[:, 0]],
                         jnp.zeros((), table.dtype))
    if weights is not None:
        weights = weights.reshape(out_rows, cap)
    total = 0.0
    for slot in range(cap):
        picked = table[rows[:, slot]].astype(jnp.float32)
        if weights is not None:
            picked = picked * weights[:, slot, None]
        total = total + jnp.where(counts[:, None] > slot, picked, 0.0)
    return total.astype(table.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _kernel(tile_entries_ref, rows_ref, counts_ref, *rest, cap: int,
            weighted: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_ref = rest[0] if weighted else None
    table_ref, out_ref, stage, acc, ent_row, ent_out, ent_w, sems = rest[-8:]
    tile_rows = out_ref.shape[0]
    tile = pl.program_id(1)
    base = tile * tile_rows         # the tile's first row in the SMEM blocks
    n = tile_entries_ref[pl.program_id(0) * pl.num_programs(1) + tile]
    # a strip's rows as the vector unit reads ONE of them: 32-bit sublanes,
    # so a 16-bit table's rows lie two to a sublane (the even one low)
    packed = 4 // stage.dtype.itemsize
    words = stage.bitcast(jnp.uint32) if packed == 2 else stage

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(n > 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

        # the tile's entries, listed: the table's row, the tile's, the weight
        def list_row(r, e):
            def list_entry(j, e):
                at = (base + r) * cap + j
                ent_row[e] = rows_ref[at]
                ent_out[e] = r
                if weighted:
                    ent_w[e] = w_ref[at]
                return e + 1
            return jax.lax.fori_loop(0, counts_ref[base + r], list_entry, e)
        jax.lax.fori_loop(0, tile_rows, list_row, jnp.int32(0))

        def copy(e):
            """Entry e's strip -> its place in the ring, on its semaphore
            (copies may end in any order)."""
            first = pl.multiple_of((ent_row[e] // STRIP) * STRIP, STRIP)
            at = e % RING
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(first, STRIP), :], stage.at[at],
                sems.at[at])

        def add(e):
            copy(e).wait()
            in_strip = ent_row[e] % STRIP
            row = words[e % RING, pl.ds(in_strip // packed, 1), :]
            if packed == 2:     # bfloat16 -> float32 is its bits, high
                row = jax.lax.bitcast_convert_type(
                    (row << (16 * (1 - in_strip % 2)).astype(jnp.uint32))
                    & jnp.uint32(0xFFFF0000), jnp.float32)
            if weighted:
                row = row * ent_w[e]
            acc[pl.ds(ent_out[e], 1), :] += row

        def start(e, _):
            copy(e).start()

        def start_and_add(e, _):
            copy(e).start()
            add(e - AHEAD)

        jax.lax.fori_loop(0, jnp.minimum(AHEAD, n), start, None)
        jax.lax.fori_loop(AHEAD, n, start_and_add, None)
        jax.lax.fori_loop(jnp.maximum(n - AHEAD, 0), n,
                          lambda e, _: add(e), None)
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _pad_to(a, size: int):
    return a if a.shape[0] == size else jnp.pad(a, (0, size - a.shape[0]))


def _pallas_gather_sum(table, rows, counts, weights):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    out_rows = counts.shape[0]
    cap = rows.shape[0] // out_rows
    h = table.shape[1]
    weighted = weights is not None
    # whole SMEM blocks of indices, whole strips of the table
    padded = -(-out_rows // META_ROWS) * META_ROWS
    counts = _pad_to(counts.astype(jnp.int32), padded)
    lists = [_pad_to(rows.astype(jnp.int32), padded * cap)]
    if weighted:
        lists.append(_pad_to(weights.astype(jnp.float32), padded * cap))
    if table.shape[0] % STRIP:
        table = jnp.pad(table, ((0, -table.shape[0] % STRIP), (0, 0)))
    tiles = META_ROWS // TILE_ROWS
    tile_entries = jnp.sum(counts.reshape(-1, TILE_ROWS), axis=1)

    def smem(size):
        return pl.BlockSpec((size,), lambda i, t, n: (i,),
                            memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_kernel, cap=cap, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(padded // META_ROWS, tiles),
            in_specs=[smem(META_ROWS * cap), smem(META_ROWS)]
            + [smem(META_ROWS * cap)] * weighted
            + [pl.BlockSpec(memory_space=pl.ANY)],   # the table: by copy
            out_specs=pl.BlockSpec((TILE_ROWS, h),
                                   lambda i, t, n: (i * tiles + t, 0)),
            scratch_shapes=[
                pltpu.VMEM((RING, STRIP, h), table.dtype),
                pltpu.VMEM((TILE_ROWS, h), jnp.float32),
                pltpu.SMEM((TILE_ROWS * cap,), jnp.int32),
                pltpu.SMEM((TILE_ROWS * cap,), jnp.int32),
                pltpu.SMEM((TILE_ROWS * cap,), jnp.float32),
                pltpu.SemaphoreType.DMA((RING,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((padded, h), table.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 << 20,
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=dispatch.interpret_mode(),
        name="row_gather_sum",
    )(tile_entries, lists[0], counts, *lists[1:], table)
    return out if padded == out_rows else out[:out_rows]


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

def _use_pallas(h: int, cap: int, dtype) -> bool:
    # whole lane tiles; a dtype whose float32 value the kernel can read off
    # a 32-bit sublane
    if h % 128 or dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if dispatch.interpret_mode():
        return True
    return dispatch.platform() == "tpu" and cap > 1


def path(h: int, cap: int, dtype=jnp.bfloat16) -> str:
    """Which way a call with a table of rows h wide in `dtype` and `cap`
    slots an out row goes: "pallas", "interpret" or "xla"."""
    if not _use_pallas(h, cap, dtype):
        return "xla"
    return "interpret" if dispatch.interpret_mode() else "pallas"


def gather_sum(table, rows, counts, weights=None):
    """table [n, h]; rows [out_rows * cap] int32: an out row's slots, its
    entries first; counts [out_rows]: how many of them are entries; weights
    like rows, float32, or None -> [out_rows, h] in table's dtype:
    out[i] = sum over j < counts[i] of weights[i, j] x table[rows[i, j]],
    summed in float32 in that order; zero where counts[i] is 0."""
    out_rows = counts.shape[0]
    if rows.ndim != 1 or rows.shape[0] % out_rows:
        raise ValueError(f"rows {rows.shape} are no whole number of slots "
                         f"for {out_rows} out rows")
    if weights is not None and weights.shape != rows.shape:
        raise ValueError(f"weights {weights.shape}, rows {rows.shape}")
    if _use_pallas(table.shape[1], rows.shape[0] // out_rows, table.dtype):
        return _pallas_gather_sum(table, rows, counts, weights)
    return _xla_gather_sum(table, rows, counts, weights)
