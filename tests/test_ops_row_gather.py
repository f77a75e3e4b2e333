"""Rows gathered by index and summed (ops/row_gather.py): the Pallas kernel,
interpreted on the CPU, and the XLA formulation, against a plain float32
loop over each out row's entries: ragged counts (no entry, one, every slot),
a tile with no entry at all, a last tile that is partly there, no entry
anywhere and every slot an entry; with and without weights; bfloat16 and
float32 tables."""

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.ops import row_gather as rg

TABLE_ROWS = 61         # no whole number of strips
WIDTH = 256
CAP = 3


@pytest.fixture(params=["interpret", "xla"])
def path(request, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET",
                       "1" if request.param == "interpret" else "")
    return request.param


def _counts(name, out_rows, cap, rng):
    counts = rng.integers(0, cap + 1, out_rows)
    if name == "ragged_with_an_empty_tile":
        counts[rg.TILE_ROWS:2 * rg.TILE_ROWS] = 0
        counts[:3] = [0, 1, cap]
    elif name == "no_entry_anywhere":
        counts[:] = 0
    elif name == "every_slot_an_entry":
        counts[:] = cap
    elif name == "one_entry_in_the_last_row":
        counts[:] = 0
        counts[-1] = 1
    return counts.astype(np.int32)


def _loop(table, rows, counts, weights, cap):
    table = np.asarray(table.astype(jnp.float32))
    out = np.zeros((len(counts), table.shape[1]), np.float32)
    for i, n in enumerate(counts):
        for j in range(n):
            w = np.float32(1.0) if weights is None else weights[i * cap + j]
            out[i] = out[i] + w * table[rows[i * cap + j]]
    return out


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("name,out_rows", [
    ("ragged_with_an_empty_tile", 3 * rg.TILE_ROWS),
    ("a_last_tile_partly_there", rg.TILE_ROWS + 37),
    ("no_entry_anywhere", 40),
    ("every_slot_an_entry", 72),
    ("one_entry_in_the_last_row", rg.TILE_ROWS + 1),
])
def test_gather_sum_matches_a_float32_loop(name, out_rows, weighted, dtype,
                                           path):
    rng = np.random.default_rng(len(name) + out_rows)
    table = jnp.asarray(rng.standard_normal((TABLE_ROWS, WIDTH)), dtype)
    counts = _counts(name, out_rows, CAP, rng)
    # the slots behind a row's count name no row of the table
    rows = rng.integers(0, TABLE_ROWS, out_rows * CAP).astype(np.int32)
    behind = (np.arange(out_rows * CAP) % CAP) >= np.repeat(counts, CAP)
    rows[behind] = 10 ** 6
    weights = (rng.standard_normal(out_rows * CAP).astype(np.float32)
               if weighted else None)
    assert rg.path(WIDTH, CAP, dtype) == path
    got = rg.gather_sum(table, jnp.asarray(rows), jnp.asarray(counts),
                        None if weights is None else jnp.asarray(weights))
    assert got.shape == (out_rows, WIDTH) and got.dtype == dtype
    want = _loop(table, rows, counts, weights, CAP)
    # the sum is float32; only the result is rounded to the table's dtype
    rounded = np.asarray(jnp.asarray(want).astype(dtype).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), rounded,
                               rtol=2 ** -7 if dtype == jnp.bfloat16
                               else 1e-6, atol=1e-6)
    assert not np.asarray(got)[counts == 0].any()


def test_a_strip_mate_may_hold_anything(path):
    """The kernel copies the 8 aligned rows around a named row: what the
    seven others hold (rows nobody placed) must not reach the sum."""
    table = np.full((16, WIDTH), np.nan, np.float32)
    table[5] = 2.0
    table[8] = 3.0
    got = rg.gather_sum(jnp.asarray(table), jnp.asarray([5, 8, 0, 0], jnp.int32),
                        jnp.asarray([2, 0], jnp.int32),
                        jnp.asarray([1.0, 0.5, 0.0, 0.0], jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(got), np.stack([np.full(WIDTH, 3.5, np.float32),
                                   np.zeros(WIDTH, np.float32)]))


def test_one_slot_a_row_is_one_gather_and_a_select(path):
    """cap = 1, no weights: `_place`'s forward.  The table's dtype goes
    through untouched."""
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.standard_normal((24, WIDTH)), jnp.bfloat16)
    rows = rng.integers(0, 24, 50).astype(np.int32)
    counts = (rng.random(50) < 0.5).astype(np.int32)
    got = rg.gather_sum(table, jnp.asarray(rows), jnp.asarray(counts))
    want = np.where(counts[:, None] > 0,
                    np.asarray(table.astype(jnp.float32))[rows], 0.0)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)


def test_off_the_tpu_only_the_interpreter_takes_the_kernel(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "")
    assert rg.path(WIDTH, CAP) == "xla"
    monkeypatch.setattr(rg.dispatch, "platform", lambda: "tpu")
    # on the chip: the kernel where an out row has slots to spare; XLA's one
    # gather where a slot is a row, and where the width is no whole lane tile
    assert rg.path(WIDTH, CAP) == "pallas"
    assert rg.path(WIDTH, 1) == "xla"
    assert rg.path(WIDTH + 64, CAP) == "xla"
    assert rg.path(WIDTH, CAP, jnp.float16) == "xla"    # not its bits, high


@pytest.mark.parametrize("bad", ["rows_not_flat", "slots_uneven", "weights"])
def test_lists_that_do_not_fit_are_refused(bad):
    table = jnp.zeros((8, WIDTH))
    rows = jnp.zeros((12,), jnp.int32)
    counts = jnp.zeros((4,), jnp.int32)
    weights = None
    if bad == "rows_not_flat":
        rows = rows.reshape(4, 3)
    elif bad == "slots_uneven":
        rows = rows[:11]
    else:
        weights = jnp.zeros((11,))
    with pytest.raises(ValueError):
        rg.gather_sum(table, rows, counts, weights)
