"""Grouped matmul: rows sorted by group, each group times its own matrix.

What a routed-expert layer needs of the chip (models/moe.py's
`routed_experts`): out[rows of group g] = x[rows of g] @ w[g], its
transpose for dx, and dw[g] = x_g^T dy_g, over however many rows each group
happens to hold.  Shapes are static, so the rows live in a buffer as long as
a bound the routing cannot exceed; the kernels COST by the rows that are
there, not by the bound.

The layout (`group_layout`).  A group's rows begin at a multiple of the row
tile `tile_m` and run on from there; the rows between a group's end and the
next tile edge are padding.  So every row tile belongs to ONE group, and the
kernels are plain matmuls whose weight block is chosen by a prefetched
scalar, `tile_group[i]`.  Tiles behind the last group's (`tiles_used` on)
hold nothing, and what their rows come out as is NOT defined (whatever the
buffer held): a caller reads only rows it placed (`models/moe.py` gathers by
position and selects).  Padding rows inside a used tile are computed like
any row, from whatever the caller put there.  A width that is half a lane
tile over a whole number of them (1856: `_off_grid`) is taken as it is: no
parameter and no buffer holds a padded column.

The walk (`_walk`; dw's grid goes the same way).  A column block's row tiles
pass before the next column block's, so the block of its group's matrix that
a program needs is the one the program before it had, until the group
changes: a call fetches each (group that holds rows, column block) ONCE,
however many tiles the group has, and the block is as wide as VMEM lets it
be (`_BLOCK_BYTES`: the whole matrix at every width traced so far, so the
rows are read once too).  A program of a tile behind the last used one
computes nothing and works on the blocks of the program before it, the last
used tile's at the same column block: no index moves, so the pipeline
fetches nothing and writes nothing for it, at any number of column blocks.
What is left of such a program is its fixed cost.

  - `grouped_matmul(x, w, layout)`: x [rows, k], w [groups, k, n] ->
    [rows, n]; `transpose_rhs=True` takes w [groups, n, k].
  - its VJP: dx = grouped_matmul(dy, w, transpose_rhs=not ...), and
    `_grouped_dw`: dw[g] = sum over g's tiles of x_tile^T dy_tile, summed in
    a float32 scratch that lives across a group's tiles and is stored once;
    a group with no row gets zeros.
  - Pallas on the TPU, interpreted where RAY_TPU_PALLAS_INTERPRET=1 asks;
    elsewhere the XLA formulation (`_xla_grouped`: a row's matrix gathered
    by its tile's group), which also defines the semantics in the tests.
  - `dispatch.taken()["grouped_matmul"]` says which path was traced,
    `["grouped_matmul.plan"]` the tiles, the row bound and the groups.

An empty group and one group holding every row are ordinary inputs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch

TILE_M = 256        # rows a tile: see PERF.md (PR 34) for the choice
_VMEM_BYTES = 64 << 20      # what a kernel here asks of VMEM at most
# The most one weight block may take: the pipeline holds two, half of what
# the kernel may ask; two row tiles, two output tiles and the float32
# product share the other half (2.8 + 2 + 2 MB at 256 x 2688 -> 1856, where
# the two blocks are 20.6).
_BLOCK_BYTES = _VMEM_BYTES // 4
_SUM_BYTES = 8 << 20        # dw's float32 sum of one block


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["starts", "sizes", "tile_group",
                                "tiles_used"],
                   meta_fields=["tile_m"])
@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """Where each group's rows lie in a buffer of `tile_group.size * tile_m`
    rows.  starts [groups]: a group's first row (a multiple of tile_m);
    sizes [groups]: its rows; tile_group [tiles]: the group a row tile
    belongs to (tiles behind the last used one repeat its group);
    tiles_used []: how many tiles hold rows."""
    starts: jax.Array
    sizes: jax.Array
    tile_group: jax.Array
    tiles_used: jax.Array
    tile_m: int


def layout_rows(rows_bound: int, groups: int, tile_m: int = TILE_M) -> int:
    """Rows of the buffer that holds up to `rows_bound` rows in `groups`
    groups, each begun at a tile's edge: every group may waste up to
    tile_m - 1 rows, and no split of rows_bound rows needs more tiles than
    ceil(rows_bound / tile_m) + groups."""
    tiles = -(-rows_bound // tile_m) + groups
    return tiles * tile_m


def group_layout(group_sizes, rows: int, tile_m: int = TILE_M) -> GroupLayout:
    """The layout of `group_sizes` [groups] int32 in a buffer of `rows` rows
    (`layout_rows`).  A few dozen integers of XLA arithmetic."""
    if rows % tile_m:
        raise ValueError(f"{rows} rows are no whole number of tiles of "
                         f"{tile_m}")
    tiles = rows // tile_m
    sizes = group_sizes.astype(jnp.int32)
    group_tiles = (sizes + (tile_m - 1)) // tile_m
    ends = jnp.cumsum(group_tiles)
    used = ends[-1]
    # tile i belongs to the first group whose tiles end behind it; tiles
    # behind the last used one repeat the last used tile's group
    tile = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                       jnp.maximum(used - 1, 0))
    tile_group = jnp.sum(tile[:, None] >= ends[None, :], axis=1,
                         dtype=jnp.int32)
    tile_group = jnp.minimum(tile_group, sizes.shape[0] - 1)
    return GroupLayout((ends - group_tiles) * tile_m, sizes, tile_group, used,
                       tile_m)


def row_groups(layout: GroupLayout):
    """(group [rows], valid [rows]): the group each row of the buffer lies
    in, and whether it is one of the group's rows (not padding, not behind
    the last group)."""
    tile_m = layout.tile_m
    rows = layout.tile_group.shape[0] * tile_m
    group = jnp.repeat(layout.tile_group, tile_m, total_repeat_length=rows)
    at = jnp.arange(rows, dtype=jnp.int32) - layout.starts[group]
    return group, (at >= 0) & (at < layout.sizes[group])


# ---------------------------------------------------------------------------
# XLA formulation: the semantics, and the path off the TPU
# ---------------------------------------------------------------------------

def _xla_grouped(x, w, layout: GroupLayout, transpose_rhs: bool):
    group, _ = row_groups(layout)
    return jnp.einsum("rk,rnk->rn" if transpose_rhs else "rk,rkn->rn",
                      x, w[group], preferred_element_type=jnp.float32
                      ).astype(x.dtype)


def _xla_dw(x, dy, layout: GroupLayout, groups: int):
    group, valid = row_groups(layout)
    onehot = ((group[:, None] == jnp.arange(groups)[None, :])
              & valid[:, None]).astype(x.dtype)
    return jnp.einsum("rg,rk,rn->gkn", onehot, x, dy,
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _block_n(k: int, n: int, limit: int) -> int:
    """Columns of a [k, n] block: all n where that fits `limit` bytes,
    else the largest multiple of 128 dividing n that does.  An n that is no
    whole number of lane tiles (`_off_grid`) has no such divisor: its blocks
    are the multiple of 128 that fits and pads the LAST block least (the
    grid is a ceiling; the columns behind n are read as whatever lies there
    and what is computed from them is never stored)."""
    if k * n <= limit or n % 128 and not _off_grid(n):
        return n
    if n % 128:
        return min((c for c in range(128, n, 128) if k * c <= limit),
                   key=lambda c: (-(-n // c) * c, -c), default=128)
    return max([128] + [c for c in range(128, n, 128)
                        if n % c == 0 and k * c <= limit])


def _off_grid(width: int) -> bool:
    """A width the kernels take though it is no whole number of lane tiles:
    half a tile over (1856 = 14 x 128 + 64).  As a contraction (k) it is a
    block's WHOLE dimension, which a block may always be, and the compiler
    masks the half tile; as n it is cut into padded blocks (`_block_n`).
    No parameter holds a padded column either way."""
    return width > 128 and width % 128 == 64


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    # both axes in order: a column block's row tiles follow one another, so
    # a group's weight block stays while its tiles pass, a tile behind the
    # last used one finds every block where the program before it left it,
    # and dw's scratch sums along the rows
    return pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_BYTES,
        dimension_semantics=("arbitrary", "arbitrary"))


def _row_tile(i, used):
    """The row tile program i works on: its own, or for a tile behind the
    last used one that one again, so that the block does not move."""
    return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))


def _walk(j, i, tile_group, used):
    """(row tile, group, column block) that program (j, i) of the grid
    (column blocks, row tiles) works on; `tile_group` [tiles] and `used` [1]
    are the prefetched scalars (or, in a test, arrays on the host).  A tile
    behind the last used one gives what the program before it gave:
    `tile_group` repeats the last used tile's group there (`group_layout`),
    the row tile is the last used one, and j is the same.  Where NO tile is
    used every program is behind the first, so the column block stays too."""
    return (_row_tile(i, used), tile_group[i],
            jnp.where(used[0] > 0, j, 0))


def _mm_kernel(tile_group_ref, used_ref, x_ref, w_ref, o_ref, *,
               transpose_rhs: bool):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _pallas_grouped(x, w, layout: GroupLayout, transpose_rhs: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile_m = layout.tile_m
    rows, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    bn = _block_n(k, n, _BLOCK_BYTES // w.dtype.itemsize)

    w_block = (1, bn, k) if transpose_rhs else (1, k, bn)

    def x_at(j, i, g, u):
        return _walk(j, i, g, u)[0], 0

    def w_at(j, i, g, u):
        _, group, column = _walk(j, i, g, u)
        return (group, column, 0) if transpose_rhs else (group, 0, column)

    def out_at(j, i, g, u):
        tile, _, column = _walk(j, i, g, u)
        return tile, column

    return pl.pallas_call(
        functools.partial(_mm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-n // bn), rows // tile_m),  # a column block, then rows
            in_specs=[pl.BlockSpec((tile_m, k), x_at),
                      pl.BlockSpec(w_block, w_at)],
            out_specs=pl.BlockSpec((tile_m, bn), out_at),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="grouped_matmul_t" if transpose_rhs else "grouped_matmul",
    )(layout.tile_group, layout.tiles_used.reshape(1), x, w)


def _dw_kernel(tile_group_ref, used_ref, x_ref, dy_ref, dw_ref, acc_ref):
    """One row tile's x^T dy, added to its group's sum.  The tiles of a
    group follow one another: the sum starts at the group's first tile and
    is stored at its last."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    used = used_ref[0]
    live = i < used
    group = tile_group_ref[i]
    first = jnp.logical_or(
        i == 0, tile_group_ref[jnp.maximum(i - 1, 0)] != group)
    last = jnp.logical_or(
        i == used - 1,
        tile_group_ref[jnp.minimum(i + 1, pl.num_programs(1) - 1)] != group)

    @pl.when(jnp.logical_and(live, first))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    @pl.when(live)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, last))
    def _():
        dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


def _pallas_dw(x, dy, layout: GroupLayout, groups: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile_m = layout.tile_m
    rows, k = x.shape
    n = dy.shape[1]
    bn = _block_n(k, n, _SUM_BYTES // 4)       # the float32 sum [k, bn]

    dw = pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-n // bn), rows // tile_m),  # a column block, then rows
            in_specs=[
                pl.BlockSpec((tile_m, k),
                             lambda j, i, g, u: (_row_tile(i, u), 0)),
                pl.BlockSpec((tile_m, bn),
                             lambda j, i, g, u: (_row_tile(i, u), j)),
            ],
            out_specs=pl.BlockSpec((1, k, bn),
                                   lambda j, i, g, u: (g[i], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), x.dtype),
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="grouped_matmul_dw",
    )(layout.tile_group, layout.tiles_used.reshape(1), x, dy)
    # a group with no row was never visited: its block holds nothing defined
    return jnp.where((layout.sizes > 0)[:, None, None], dw,
                     jnp.zeros((), dw.dtype))


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

def _use_pallas(k: int, n: int, tile_m: int) -> bool:
    if dispatch.interpret_mode():
        return True
    return (dispatch.platform() == "tpu" and tile_m % 16 == 0
            and all(w % 128 == 0 or _off_grid(w) for w in (k, n)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(x, w, layout, transpose_rhs: bool, pallas: bool):
    fn = _pallas_grouped if pallas else _xla_grouped
    return fn(x, w, layout, transpose_rhs)


def _grouped_fwd(x, w, layout, transpose_rhs, pallas):
    return _grouped(x, w, layout, transpose_rhs, pallas), (x, w, layout)


def _grouped_bwd(transpose_rhs, pallas, res, dy):
    x, w, layout = res
    dx = _grouped(dy, w, layout, not transpose_rhs, pallas)
    dw_fn = _pallas_dw if pallas else _xla_dw
    # w [g, k, n]: x^T dy; w [g, n, k] (transposed): dy^T x
    dw = dw_fn(dy, x, layout, w.shape[0]) if transpose_rhs \
        else dw_fn(x, dy, layout, w.shape[0])
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, layout: GroupLayout, transpose_rhs: bool = False):
    """x [rows, k] in `layout` (rows sorted by group, a group begun at a
    tile's edge), w [groups, k, n] (or [groups, n, k] with transpose_rhs)
    -> [rows, n]: each row times its group's matrix, float32 accumulation,
    the result in x's dtype.  Rows behind the last group's tiles come out
    undefined (the module's header); differentiable in x and w."""
    tile_m = layout.tile_m
    if x.shape[0] != layout.tile_group.shape[0] * tile_m:
        raise ValueError(f"x has {x.shape[0]} rows, the layout "
                         f"{layout.tile_group.shape[0]} tiles of {tile_m}")
    k, n = x.shape[1], w.shape[1] if transpose_rhs else w.shape[2]
    pallas = _use_pallas(k, n, tile_m)
    dispatch.record("grouped_matmul", "xla" if not pallas else
                    "interpret" if dispatch.interpret_mode() else "pallas")
    bn = _block_n(k, n, _BLOCK_BYTES // w.dtype.itemsize)
    off = "".join(f",{name}{width}_{how}" for name, width, how in (
        ("k", k, "whole"),
        ("n", n, "whole" if bn == n else "last_block_padded"))
        if _off_grid(width))
    dispatch.record("grouped_matmul.plan",
                    f"tile{tile_m}x{bn},rows{x.shape[0]},groups{w.shape[0]}"
                    f"{off}")
    return _grouped(x, w, layout, transpose_rhs, pallas)
