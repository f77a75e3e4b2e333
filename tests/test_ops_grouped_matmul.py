"""Grouped matmul (ops/grouped_matmul.py): the Pallas kernels, interpreted
on the CPU, and the XLA formulation, against a plain einsum over each row's
own group: forward, dx and dw; uneven groups, an empty group, every row in
one group, no row at all, and the row bound reached; the same with the
matrices cut into two and three column blocks; and the walk over the grid
itself (`_walk`), evaluated on the host: what moves from one program to the
next."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_matmul as gm

TILE = 16
GROUPS = 5
BOUND = 55      # the most rows the five groups may hold together
NARROW_BYTES = 128 * 128 * 4    # a block of 128 columns of `_case`'s matrices

# group sizes -> what the case is
_SIZES = {
    "uneven": [5, 2, 33, 14, 1],
    "an_empty_group": [20, 0, 17, 0, 18],
    "every_row_to_one_group": [0, 0, 55, 0, 0],
    "every_row_to_the_last_group": [0, 0, 0, 0, 55],
    "the_bound_reached_evenly": [11, 11, 11, 11, 11],
    "tile_edges": [16, 32, 1, 0, 6],
    "no_row_at_all": [0, 0, 0, 0, 0],
}


@pytest.fixture(params=["interpret", "xla"])
def path(request, monkeypatch):
    """The path a test takes.  `narrow` is the interpreter with the byte
    limit cut down to blocks of 128 columns of `_case`'s float32 matrices:
    the cells' matrices are one block each, so the walk over several column
    blocks is held here."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET",
                       "" if request.param == "xla" else "1")
    if request.param == "narrow":
        monkeypatch.setattr(gm, "_BLOCK_BYTES", NARROW_BYTES)
        return "interpret"
    return request.param


# (group sizes, path, n): every split on both paths at one block, and the
# splits that leave most tiles empty at two and three
_ONE_BLOCK = [(name, path, 256) for name in sorted(_SIZES)
              for path in ("interpret", "xla")]
_MORE_BLOCKS = [(name, "narrow", n) for n in (256, 384) for name in (
    "an_empty_group", "every_row_to_one_group", "no_row_at_all")]


def _case(sizes, k=128, n=256, seed=0):
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = gm.layout_rows(BOUND, GROUPS, TILE)
    layout = gm.group_layout(sizes, rows, TILE)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (rows, k), jnp.float32)
    w = jax.random.normal(ks[1], (GROUPS, k, n), jnp.float32)
    c = jax.random.normal(ks[2], (rows, n), jnp.float32)
    return layout, x, w, c


def _einsum_reference(x, w, layout):
    group, valid = gm.row_groups(layout)
    return jnp.where(valid[:, None],
                     jnp.einsum("rk,rkn->rn", x, w[group]), 0.0), valid


@pytest.mark.parametrize("name,path,n", _ONE_BLOCK + _MORE_BLOCKS,
                         indirect=["path"])
def test_forward_dx_and_dw_match_an_einsum(name, path, n):
    layout, x, w, c = _case(_SIZES[name], n=n)
    if gm._BLOCK_BYTES == NARROW_BYTES:     # n is two or three blocks
        assert gm._block_n(128, n, NARROW_BYTES // 4) == 128 < n
    _, valid = gm.row_groups(layout)
    assert int(valid.sum()) == sum(_SIZES[name])      # no row left out

    def loss(fn):
        def f(x, w):
            out = fn(x, w)
            # rows nobody placed hold nothing defined: select, never multiply
            return jnp.sum(jnp.where(valid[:, None], out * c, 0.0)), out
        return f

    (got, out), (dx, dw) = jax.value_and_grad(
        loss(lambda x, w: gm.grouped_matmul(x, w, layout)), (0, 1),
        has_aux=True)(x, w)
    (want, ref), (rx, rw) = jax.value_and_grad(
        loss(lambda x, w: _einsum_reference(x, w, layout)[0]), (0, 1),
        has_aux=True)(x, w)
    np.testing.assert_allclose(
        np.asarray(jnp.where(valid[:, None], out, 0.0)), np.asarray(ref),
        atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(jnp.where(valid[:, None], dx, 0.0)), np.asarray(rx),
        atol=1e-4, rtol=1e-5)
    # dw is defined for EVERY group: zeros where a group holds no row
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rw),
                               atol=2e-4, rtol=1e-5)
    for g, size in enumerate(_SIZES[name]):
        if size == 0:
            assert not np.asarray(dw[g]).any()


@pytest.mark.parametrize("name,path,n", [
    (name, path, 256) for name in ("uneven", "an_empty_group")
    for path in ("interpret", "xla")] + [
    (name, "narrow", n) for n in (256, 384)
    for name in ("uneven", "an_empty_group")], indirect=["path"])
def test_transposed_matrices_give_the_same_product(name, path, n):
    layout, x, w, _ = _case(_SIZES[name], n=n)
    _, valid = gm.row_groups(layout)
    a = gm.grouped_matmul(x, w, layout)
    b = gm.grouped_matmul(x, w.transpose(0, 2, 1), layout, transpose_rhs=True)
    np.testing.assert_allclose(np.asarray(jnp.where(valid[:, None], a, 0)),
                               np.asarray(jnp.where(valid[:, None], b, 0)),
                               atol=1e-4, rtol=1e-5)


# group sizes -> the tiles of the buffer (None: `layout_rows`' nine)
_WALKS = {
    "uneven": ([5, 2, 33, 14, 1], None),
    "an_empty_group_in_the_middle": ([20, 0, 17, 0, 18], None),
    "an_empty_last_group": ([20, 3, 17, 15, 0], None),
    "no_used_tile": ([0, 0, 0, 0, 0], None),
    "a_full_buffer": ([16, 32, 1, 0, 6], 5),
}


@pytest.mark.parametrize("blocks", [1, 2, 3])
@pytest.mark.parametrize("name", sorted(_WALKS))
def test_the_walk_fetches_a_block_once_and_moves_nothing_for_an_empty_tile(
        name, blocks):
    """`_walk` over the whole grid (column blocks, row tiles), in the order
    the programs run: the pipeline fetches a block where its index differs
    from the program's before, and writes an output block back where the
    next program's differs."""
    sizes, tiles = _WALKS[name]
    tiles = tiles or gm.layout_rows(BOUND, GROUPS, TILE) // TILE
    layout = gm.group_layout(jnp.asarray(sizes, jnp.int32), tiles * TILE, TILE)
    used = int(layout.tiles_used)
    assert used == sum(-(-s // TILE) for s in sizes) <= tiles
    assert (used == tiles) == (name == "a_full_buffer")
    j, i = (v.reshape(-1) for v in np.meshgrid(
        np.arange(blocks), np.arange(tiles), indexing="ij"))
    tile, group, column = (np.broadcast_to(np.asarray(v), i.shape) for v in
                           gm._walk(j, i, layout.tile_group,
                                    layout.tiles_used.reshape(1)))
    x_at = np.stack([tile], 1)
    w_at = np.stack([group, column], 1)
    out_at = np.stack([tile, column], 1)
    # a used tile's program works on its own tile, its group, its column
    live = i < used
    tile_group = np.asarray(layout.tile_group)
    assert (tile[live] == i[live]).all() and (column[live] == j[live]).all()
    assert (group[live] == tile_group[i[live]]).all()
    assert all(sizes[g] > 0 for g in group[live])
    # a program behind the last used tile: nothing moves
    behind = ~live
    behind[0] = False                   # the first program has none before
    for at in (x_at, w_at, out_at):
        assert (at[behind] == at[np.flatnonzero(behind) - 1]).all()
    # a weight block is fetched once a (group that holds rows, column block)
    fetches = 1 + int((w_at[1:] != w_at[:-1]).any(axis=1).sum())
    held = sum(s > 0 for s in sizes)
    assert fetches == max(held * blocks, 1)
    # and an output block is written back once, behind its one program
    writes = 1 + int((out_at[1:] != out_at[:-1]).any(axis=1).sum())
    assert writes == max(used * blocks, 1)


def test_layout_begins_every_group_at_a_tile_and_fits_any_split():
    """`layout_rows` holds every split of the bound: the worst case is each
    group one row over a tile's edge."""
    rows = gm.layout_rows(BOUND, GROUPS, TILE)
    assert rows % TILE == 0
    for sizes in _SIZES.values():
        layout = gm.group_layout(jnp.asarray(sizes, jnp.int32), rows, TILE)
        starts = np.asarray(layout.starts)
        assert (starts % TILE == 0).all()
        ends = starts + np.asarray(sizes)
        assert (ends[:-1] <= starts[1:]).all() and ends[-1] <= rows
        used = int(layout.tiles_used)
        assert used == sum(-(-s // TILE) for s in sizes) <= rows // TILE
        tile_group = np.asarray(layout.tile_group)
        for g, (start, size) in enumerate(zip(starts, sizes)):
            tiles = range(start // TILE, start // TILE + -(-size // TILE))
            assert all(tile_group[t] == g for t in tiles)
    worst = [17, 17, 17, 3, 1]      # 55 rows, five tiles' edges crossed
    layout = gm.group_layout(jnp.asarray(worst, jnp.int32), rows, TILE)
    assert int(layout.tiles_used) == 2 + 2 + 2 + 1 + 1 <= rows // TILE


def test_path_and_plan_are_recorded(path, monkeypatch):
    monkeypatch.setattr(gm.dispatch, "_taken", {})
    layout, x, w, _ = _case(_SIZES["uneven"])
    gm.grouped_matmul(x, w, layout)
    taken = gm.dispatch.taken()
    assert taken["grouped_matmul"] == {path: 1}
    rows = gm.layout_rows(BOUND, GROUPS, TILE)
    assert list(taken["grouped_matmul.plan"]) == [
        f"tile{TILE}x256,rows{rows},groups{GROUPS}"]


def test_bfloat16_rows_accumulate_in_float32(path):
    layout, x, w, _ = _case(_SIZES["the_bound_reached_evenly"], k=256)
    _, valid = gm.row_groups(layout)
    out = gm.grouped_matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                            layout)
    assert out.dtype == jnp.bfloat16
    ref, _ = _einsum_reference(x.astype(jnp.bfloat16).astype(jnp.float32),
                               w.astype(jnp.bfloat16).astype(jnp.float32),
                               layout)
    err = jnp.where(valid[:, None], out.astype(jnp.float32) - ref, 0.0)
    assert float(jnp.linalg.norm(err) / jnp.linalg.norm(ref)) < 4e-3
