"""Flagship transformer: forward/loss sanity + sharded step on virtual mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import transformer as tfm
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel import sharding


@pytest.fixture(scope="module")
def tiny():
    return tfm.TransformerConfig.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(tiny):
    return tfm.init_params(tiny, jax.random.PRNGKey(0))


def test_forward_shapes(tiny, params):
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = tfm.forward(params, tokens, tiny)
    assert logits.shape == (2, 16, tiny.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_loss_decreases_under_sgd(tiny, params):
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (4, 33), 0, tiny.vocab_size)
    batch = {"tokens": tokens}

    @jax.jit
    def step(p):
        loss, grads = jax.value_and_grad(tfm.loss_fn)(p, batch, tiny)
        new_p = jax.tree.map(lambda w, g: w - 0.5 * g, p, grads)
        return new_p, loss

    p = params
    losses = []
    for _ in range(5):
        p, loss = step(p)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_scan_matches_unrolled(tiny):
    """scan-over-layers and unrolled layers compute the same function."""
    cfg_scan = tiny
    cfg_unroll = tfm.TransformerConfig.tiny(dtype=jnp.float32,
                                            scan_layers=False, remat=False)
    p_scan = tfm.init_params(cfg_scan, jax.random.PRNGKey(7))
    # Restack scan params into per-layer for the unrolled config: for 1-layer
    # comparison use num_layers=1 variants instead (cheaper).
    cfg_s1 = tfm.TransformerConfig.tiny(dtype=jnp.float32, num_layers=1)
    cfg_u1 = tfm.TransformerConfig.tiny(dtype=jnp.float32, num_layers=1,
                                        scan_layers=False, remat=False)
    p1 = tfm.init_params(cfg_s1, jax.random.PRNGKey(7))
    p1_unroll = {
        "tok_embed": p1["tok_embed"],
        "blocks": jax.tree.map(lambda x: x[0], p1["blocks"]),
        "final_norm": p1["final_norm"],
    }
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 256)
    out_s = tfm.forward(p1, tokens, cfg_s1)
    out_u = tfm.forward(p1_unroll, tokens, cfg_u1)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_u),
                               atol=1e-5, rtol=1e-5)


def test_sharded_train_step_on_virtual_mesh(tiny, params):
    """Full GSPMD train step over an 8-device mesh (dp=2, fsdp=2, tp=2)."""
    mesh = mesh_lib.build_mesh(axes={"data": 2, "fsdp": 2, "tensor": 2})
    assert mesh.devices.size == 8

    logical = tfm.logical_axes(tiny)
    sharded = sharding.shard_tree(params, mesh, logical_tree=logical)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 33), 0,
                                tiny.vocab_size)
    batch = {"tokens": jax.device_put(
        tokens, sharding.data_sharding(mesh))}

    @jax.jit
    def step(p, b):
        loss, grads = jax.value_and_grad(tfm.loss_fn)(p, b, tiny)
        return jax.tree.map(lambda w, g: w - 0.1 * g, p, grads), loss

    with jax.sharding.set_mesh(mesh):
        new_p, loss = step(sharded, batch)
    assert np.isfinite(float(loss))
    # params keep their shardings
    wq = new_p["blocks"]["wq"]
    assert not wq.sharding.is_fully_replicated


def test_param_count_formula(tiny, params):
    actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert actual == tfm.num_params(tiny)


def test_llama2_7b_compiles_at_shape():
    """Round-1 verdict W3: the 7B flagship config was never even
    shape-checked.  jax.eval_shape traces init + the full training loss
    at the REAL 7B shapes (zero memory, zero FLOPs) so a shape bug in
    the big config can't hide behind the tiny test configs."""
    config = tfm.TransformerConfig.llama2_7b()
    assert tfm.num_params(config) > 6.5e9

    param_shapes = jax.eval_shape(
        lambda key: tfm.init_params(config, key), jax.random.key(0))
    wq = param_shapes["blocks"]["wq"]
    assert wq.shape == (32, 4096, 4096)
    total = sum(int(np.prod(s.shape))
                for s in jax.tree.leaves(param_shapes))
    assert total == tfm.num_params(config)

    batch = {"tokens": jax.ShapeDtypeStruct((2, 4097), jnp.int32)}
    loss_shape = jax.eval_shape(
        lambda p, b: tfm.loss_fn(p, b, config), param_shapes, batch)
    assert loss_shape.shape == ()
    # Gradients trace at shape too (the training step's real surface).
    grad_shapes = jax.eval_shape(
        lambda p, b: jax.grad(lambda q: tfm.loss_fn(q, b, config))(p),
        param_shapes, batch)
    assert grad_shapes["tok_embed"].shape == (32000, 4096)


# ---------------------------------------------------------------------------
# Rope inside the flash kernels: `_block` hands q and k as projected where
# flash_attention will run, and ropes them itself everywhere else
# ---------------------------------------------------------------------------

def _flash_and_plain(**kw):
    """The tiny model twice: through flash_attention (rope in the kernels)
    and with use_flash=False (rope in XLA, plain attention)."""
    base = dict(dtype=jnp.float32, max_seq_len=256, head_dim=32, **kw)
    return (tfm.TransformerConfig.tiny(use_flash=True, **base),
            tfm.TransformerConfig.tiny(use_flash=False, **base))


def _new_flash_plans(before):
    from ray_tpu.ops import dispatch

    return [p for p, n in dispatch.taken().get(
        "flash_attention.plan", {}).items()
        if n > before.get("flash_attention.plan", {}).get(p, 0)]


@pytest.mark.parametrize("remat_policy", ["full", "save_attn"])
@pytest.mark.parametrize("kv_heads", [4, 2])      # without GQA's repeat, with
def test_loss_and_gradients_with_rope_in_the_kernel_match_the_plain_path(
        monkeypatch, kv_heads, remat_policy):
    from ray_tpu.ops import dispatch

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    flash, plain = _flash_and_plain(num_kv_heads=kv_heads,
                                    remat_policy=remat_policy)
    params = tfm.init_params(flash, jax.random.PRNGKey(3))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (2, 257), 0,
                                          flash.vocab_size)}
    before = dispatch.taken()
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, batch, flash)))(params)
    plans = _new_flash_plans(before)
    assert plans and all(",rope_in_kernel,operands_bshd," in p
                         for p in plans), plans
    before = dispatch.taken()
    loss_p, grads_p = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, batch, plain)))(params)
    assert not _new_flash_plans(before)
    np.testing.assert_allclose(float(loss), float(loss_p), rtol=1e-5)
    for (path, g), g_p in zip(jax.tree_util.tree_leaves_with_path(grads),
                              jax.tree.leaves(grads_p)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_p), atol=2e-5,
                                   rtol=2e-3, err_msg=str(path))


def test_positions_reach_the_kernels_tables(monkeypatch):
    """forward() at positions that differ by row, do not start at 0 and, in
    one row, step by 2 (rope is relative: a row moved as a whole attends as
    before): the kernels' tables are gathered at them, as rope in XLA's."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    flash, plain = _flash_and_plain()
    params = tfm.init_params(flash, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0,
                                flash.vocab_size)
    positions = jnp.stack([2 * jnp.arange(128) + 1, jnp.arange(128) + 100])
    got = tfm.forward(params, tokens, flash, positions=positions)
    want = tfm.forward(params, tokens, plain, positions=positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)
    moved = tfm.forward(params, tokens, flash)
    assert np.abs(np.asarray(moved) - np.asarray(want)).max() > 1e-2


def test_pipelined_forward_and_eval_step_run_the_same_block(monkeypatch):
    """forward_pipelined (its stage function calls `_block`) and
    ShardedTrainStep.eval_step, each through the roped kernels, against
    the plain path."""
    from ray_tpu.ops import dispatch
    from ray_tpu.train.train_state import ShardedTrainStep

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    flash, plain = _flash_and_plain()
    params = tfm.init_params(flash, jax.random.PRNGKey(7))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(8), (4, 129), 0,
                                          flash.vocab_size)}
    mesh = mesh_lib.build_mesh(axes={"stage": 2},
                               devices=jax.devices()[:2])
    before = dispatch.taken()
    with jax.sharding.set_mesh(mesh):
        piped = jax.jit(lambda p: tfm.loss_fn_pipelined(
            p, batch, flash, 2, mesh=mesh))(params)
    assert all(",rope_in_kernel,operands_bshd," in p
               for p in _new_flash_plans(before))
    want = float(tfm.loss_fn(params, batch, plain))
    np.testing.assert_allclose(float(piped), want, rtol=1e-5)

    one = mesh_lib.build_mesh(axes={"data": 1}, devices=jax.devices()[:1])
    before = dispatch.taken()
    got = ShardedTrainStep(flash, one).eval_step(params, batch)
    assert _new_flash_plans(before)
    np.testing.assert_allclose(float(got), want, rtol=1e-5)
