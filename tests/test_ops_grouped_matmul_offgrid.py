"""ops/grouped_matmul.py at widths that are NO whole number of lane tiles
(1856 = 14 x 128 + 64: half a tile over), as k (a block's whole dimension)
and as n (padded blocks: the grid is a ceiling, nothing behind n is stored),
kernels interpreted on the CPU against `_xla_grouped`: forward, dx, dw, an
empty group, one group holding every row; the blocks and plans of the faces
the older cells trace, word for word; and models/moe.py's `routed_experts`
with no gate matrix against a loop over experts."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import common, moe
from ray_tpu.ops import dispatch, grouped_matmul as gm

TILE = 16
WIDE, NARROW = 256, 192         # 192 = 128 + 64: half a lane tile over


def _case(sizes, k, n, seed=0):
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = gm.layout_rows(int(sizes.sum()), sizes.shape[0], TILE)
    layout = gm.group_layout(sizes, rows, TILE)
    kx, kw = jax.random.split(jax.random.key(seed))
    _, valid = gm.row_groups(layout)
    x = jnp.where(valid[:, None], jax.random.normal(kx, (rows, k)), 0.0)
    w = jax.random.normal(kw, (sizes.shape[0], k, n)) / np.sqrt(k)
    return x, w, layout, valid


SPLITS = {"uneven": [40, 7, 0, 33], "empty_first": [0, 50, 30, 0],
          "one_holds_all": [0, 0, 80, 0]}


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of at most 128 x k and sums of 128 x k: the off-grid n is cut
    into two blocks, the second padded."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(gm, "_BLOCK_BYTES", WIDE * 128 * 4)
    monkeypatch.setattr(gm, "_SUM_BYTES", WIDE * 128 * 4)


@pytest.mark.parametrize("split", sorted(SPLITS))
@pytest.mark.parametrize("k,n", [(WIDE, NARROW), (NARROW, WIDE)])
def test_forward_dx_and_dw_match_the_xla_formulation(split, k, n,
                                                     small_blocks):
    x, w, layout, valid = _case(SPLITS[split], k, n)
    assert gm._block_n(k, n, gm._BLOCK_BYTES // 4) == 128
    got = gm.grouped_matmul(x, w, layout)
    want = gm._xla_grouped(x, w, layout, False)
    used = np.asarray(jnp.arange(x.shape[0])
                      < layout.tiles_used * TILE)[:, None]
    np.testing.assert_allclose(np.where(used, got, 0),
                               np.where(used, want, 0), atol=2e-5)
    cot = jnp.where(valid[:, None],
                    jax.random.normal(jax.random.key(5), got.shape), 0.0)

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.where(used, fn(x, w), 0.0) * cot)

    dx, dw = jax.jit(jax.grad(
        loss(lambda x, w: gm.grouped_matmul(x, w, layout)), (0, 1)))(x, w)
    rx, rw = jax.jit(jax.grad(
        loss(lambda x, w: gm._xla_grouped(x, w, layout, False)), (0, 1)))(
            x, w)
    np.testing.assert_allclose(np.where(used, dx, 0), np.where(used, rx, 0),
                               atol=2e-5)
    np.testing.assert_allclose(dw, rw, atol=2e-4)
    assert dw.shape == w.shape and bool(jnp.all(jnp.isfinite(dw)))
    assert not np.asarray(dw)[np.asarray(SPLITS[split]) == 0].any()


def test_the_blocks_of_the_cell_s_faces_and_what_the_plan_says(monkeypatch):
    """2688 -> 1856 and back, bfloat16: the whole matrix is one block since
    PR 50 (9.5 MiB of the 16 a block may take), the off-grid width a block's
    whole dimension either way.  Where it does not fit (dw's float32 sums of
    8 MiB; a forward block held to 4 MiB, as before PR 50) n off the grid is
    cut into blocks of 640 columns, three of them, the last padded by 64,
    and k off the grid stays whole beside 896 columns.  Nothing is stored in
    a parameter: the widths in the plan are the published ones."""
    limit = gm._BLOCK_BYTES // 2
    assert gm._block_n(2688, 1856, limit) == 1856
    assert gm._block_n(1856, 2688, limit) == 2688
    assert gm._block_n(2688, 1856, (4 << 20) // 2) == 640
    assert gm._block_n(1856, 2688, (4 << 20) // 2) == 896
    assert gm._block_n(2688, 1856, gm._SUM_BYTES // 4) == 640
    assert gm._block_n(1856, 2688, gm._SUM_BYTES // 4) == 896
    assert gm._off_grid(1856) and not gm._off_grid(1792) \
        and not gm._off_grid(1800) and not gm._off_grid(64)
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    assert gm._use_pallas(2688, 1856, 256) and gm._use_pallas(1856, 2688, 256)
    assert not gm._use_pallas(2688, 1800, 256)
    monkeypatch.undo()
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(dispatch, "_taken", {})
    x, w, layout, _ = _case([20, 12], WIDE, NARROW)
    gm.grouped_matmul(x, w, layout)
    gm.grouped_matmul(x[:, :NARROW], jnp.swapaxes(w, 1, 2), layout)
    assert list(dispatch.taken()["grouped_matmul.plan"]) == [
        "tile16x192,rows64,groups2,n192_whole",
        "tile16x256,rows64,groups2,k192_whole"]
    # cut into two blocks of 128 columns the plan says which one is padded
    monkeypatch.setattr(dispatch, "_taken", {})
    monkeypatch.setattr(gm, "_BLOCK_BYTES", WIDE * 128 * 4)
    gm.grouped_matmul(x, w, layout)
    assert list(dispatch.taken()["grouped_matmul.plan"]) == [
        "tile16x128,rows64,groups2,n192_last_block_padded"]


@pytest.mark.parametrize("k,n,block,dw_block", [
    (2048, 768, 768, 768), (768, 2048, 2048, 2048),     # train-moe-mla-d6
    (3072, 1024, 1024, 512), (1024, 3072, 3072, 1536),  # train-swa-moe-d5
    (2048, 512, 512, 512), (512, 2048, 2048, 2048),     # train-gdn-moe-d4
    (2048, 2048, 2048, 1024),                           # train-cca-moe-d4
    (64, 32, 32, 32), (32, 64, 64, 64)])                # the tiny sizes
def test_the_older_faces_blocks_are_the_ones_chosen_before(k, n, block,
                                                           dw_block):
    """`_block_n`'s rule as PR 47's tree had it, for every face the four
    older expert cells trace and for a width that is no multiple of 128 and
    not half a tile over one.  What moved with PR 50 is the limit of the
    forward / transposed kernel (bfloat16 blocks of 16 MiB where they were
    4: every face here is ONE block now; dw's float32 sums of 8 stand)."""
    def parents(k, n, limit):
        if k * n <= limit or n % 128:
            return n
        return max([128] + [c for c in range(128, n, 128)
                            if n % c == 0 and k * c <= limit])

    for limit, want in ((gm._BLOCK_BYTES // 2, block),
                        (gm._SUM_BYTES // 4, dw_block)):
        assert gm._block_n(k, n, limit) == parents(k, n, limit) == want


@pytest.mark.parametrize("k,n,plan", [
    (256, 128, "tile16x128,rows64,groups2"),
    (128, 256, "tile16x256,rows64,groups2"),
    (64, 32, "tile16x32,rows64,groups2")])
def test_the_older_faces_plans_word_for_word(k, n, plan, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(dispatch, "_taken", {})
    x, w, layout, _ = _case([20, 12], k, n)
    gm.grouped_matmul(x, w, layout)
    assert dispatch.taken()["grouped_matmul.plan"] == {plan: 1}


@pytest.mark.parametrize("first,count", [(0, 4), (12, 4)])
def test_ungated_routed_experts_match_a_loop_over_experts(first, count,
                                                          small_blocks):
    """`routed_experts` with `w_gate=None`: the held experts' sum of gate x
    relu(x W_up)^2 W_down, against every held expert on every token with a
    mask; and its gradients.  With a gate matrix the layer is the SwiGLU it
    was."""
    T, h, m, k, width = 48, 64, NARROW, 3, 16
    ks = jax.random.split(jax.random.key(first + count), 5)
    x = jax.random.normal(ks[0], (T, h))
    idx = jnp.argsort(jax.random.uniform(ks[1], (T, width)), axis=1)[:, :k]
    gates = jax.random.uniform(ks[2], (T, k), minval=0.1)
    w_up = jax.random.normal(ks[3], (count, h, m)) / np.sqrt(h)
    w_down = jax.random.normal(ks[4], (count, m, h)) / np.sqrt(m)

    def layer(x, gates, w_gate, w_up, w_down):
        return moe.routed_experts(
            x, idx.astype(jnp.int32), gates, w_gate, w_up, w_down,
            experts_held=(first, count), dtype=jnp.float32, tile_m=TILE)[0]

    def loop(x, gates, w_gate, w_up, w_down):
        y = jnp.zeros_like(x)
        for e in range(count):
            gate = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=1)
            hidden = common.relu2(x @ w_up[e]) if w_gate is None \
                else jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
            y = y + gate[:, None] * (hidden @ w_down[e])
        return y

    for w_gate in (None, w_up[:, :, ::-1]):
        args = (x, gates, w_gate, w_up, w_down)
        np.testing.assert_allclose(layer(*args), loop(*args), atol=3e-5)
        if w_gate is not None:
            continue
        which = (0, 1, 3, 4)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(layer(*a) ** 2), which))(
            *args)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(loop(*a) ** 2), which))(
            *args)
        for g, r in zip(got, want):
            np.testing.assert_allclose(g, r, atol=2e-3, rtol=2e-4)
