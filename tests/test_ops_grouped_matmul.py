"""Grouped matmul (ops/grouped_matmul.py): the Pallas kernels, interpreted
on the CPU, and the XLA formulation, against a plain einsum over each row's
own group: forward, dx and dw; uneven groups, an empty group, every row in
one group, no row at all, and the row bound reached."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_matmul as gm

TILE = 16
GROUPS = 5
BOUND = 55      # the most rows the five groups may hold together

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
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET",
                       "1" if request.param == "interpret" else "")
    return request.param


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


@pytest.mark.parametrize("name", sorted(_SIZES))
def test_forward_dx_and_dw_match_an_einsum(name, path):
    layout, x, w, c = _case(_SIZES[name])
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


@pytest.mark.parametrize("name", ["uneven", "an_empty_group"])
def test_transposed_matrices_give_the_same_product(name, path):
    layout, x, w, _ = _case(_SIZES[name])
    _, valid = gm.row_groups(layout)
    a = gm.grouped_matmul(x, w, layout)
    b = gm.grouped_matmul(x, w.transpose(0, 2, 1), layout, transpose_rhs=True)
    np.testing.assert_allclose(np.asarray(jnp.where(valid[:, None], a, 0)),
                               np.asarray(jnp.where(valid[:, None], b, 0)),
                               atol=1e-4, rtol=1e-5)


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
