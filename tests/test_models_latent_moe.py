"""models/latent_moe.py (latent attention + dropless routed experts) at tiny
widths, kernels interpreted on the CPU, against the benchmark's plain
reference (benchmark/reference/deepseek_v3_mla_moe.py) on seeded weights.
The form Xing4.0-29B-A4B publishes (a query latent, yarn, a residual stream
of four lanes, a second loss) is tests/test_models_latent_moe_lanes.py."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v3_mla_moe as ref
from ray_tpu.models import common, latent_moe as lm, moe, stack
from ray_tpu.ops import grouped_matmul as gm


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


# The program's per-token loss as ONE jitted function for the file: cases
# with an equal configuration share its compile, where a call dispatched
# primitive by primitive compiles every layer scan anew (the case that counts
# the kernels' calls keeps the eager call).  The init stays eager: a leaf's
# draw is cached by its shape across cases and configurations, which one
# jitted init a configuration is not.
_nll = jax.jit(lm.token_nll, static_argnums=2)


def _f32(**kw):
    return lm.LatentMoEConfig.tiny(dtype=jnp.float32, remat=False, **kw)


def _dims(config):
    return ref.dims_from_config(
        {f.name: getattr(config, f.name) for f in dataclasses.fields(config)})


def _tokens(rows=2, seq=128, vocab=256, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (rows, seq + 1), 0, vocab))


@pytest.mark.parametrize("fused_ce", [False, True])
def test_token_nll_matches_the_reference(fused_ce):
    config = _f32(fused_ce=fused_ce)
    params = lm.init_params(config, jax.random.PRNGKey(3))
    tokens = _tokens()
    got = _nll(params, {"tokens": jnp.asarray(tokens)}, config)
    want = ref.batch_token_nll(params, tokens, _dims(config))
    # the fused cross-entropy multiplies in bfloat16 whatever the model's
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2 if fused_ce else 2e-4)
    assert abs(float(got.mean()) - np.log(256)) < 1.0


def test_gradient_matches_the_reference_layer_by_layer():
    """jax.grad of the program's loss (the grouped kernels' and the flash
    kernels' VJPs, the gathers' custom VJPs) against the reference's
    gradient walked back a layer at a time; the selection bias gets none."""
    config = _f32()
    params = lm.init_params(config, jax.random.PRNGKey(4))
    tokens = _tokens()
    got = jax.jit(jax.grad(lambda p: lm.loss_fn(
        p, {"tokens": jnp.asarray(tokens)}, config)))(params)
    want = {}
    for row in tokens:
        run = ref.Pass(params, row[:-1], _dims(config), for_grads=True)
        for path, grad in run.grads(row[1:]):
            for name, g in (grad.items() if isinstance(grad, dict)
                            else [(None, grad)]):
                want[path, name] = want.get((path, name), 0) \
                    + np.asarray(g) / len(tokens)
    seen = 0
    for (path, name), w in want.items():
        g = got[path[0]] if name is None \
            else got["layers"][path[1]][path[2]][name][path[3]]
        g, norm = np.asarray(g), np.linalg.norm(w)
        if name == "router_bias":
            assert norm == 0 and not g.any()
            continue
        assert np.linalg.norm(g - w) <= 2e-4 * norm, (path, name)
        seen += 1
    # three at the top, a dense layer's ten, two expert layers' fifteen each
    assert len(want) == 3 + 10 + 2 * 15 and seen == len(want) - 2


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips, two of sixteen experts each: the routed parts that the
    program's layer gives for the eight shares, plus the shared expert
    counted ONCE, are what the uncut sixteen-expert reference layer gives."""
    config = _f32(n_routed_experts=16, router_width=16)
    lp = jax.tree.map(
        lambda a: a[0], lm.init_params(config, jax.random.PRNGKey(5))
        ["layers"]["seg01"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(6), (96, config.hidden_size))
    idx, gates = moe.sigmoid_route(
        h, lp["router_w"], lp["router_bias"],
        num_experts_per_token=config.num_experts_per_tok,
        scale=config.routed_scaling_factor)
    total, rows = 0.0, 0
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        part, stats = moe.routed_experts(
            h, idx, gates, lp["experts_gate"][held], lp["experts_up"][held],
            lp["experts_down"][held], experts_held=(2 * share, 2),
            dtype=jnp.float32, tile_m=16)
        total, rows = total + part, rows + int(stats["rows_held"])
    assert rows == 96 * config.num_experts_per_tok      # every assignment
    total = total + common.swiglu(h, lp["shared_gate"], lp["shared_up"],
                                  lp["shared_down"], jnp.float32)
    d = _dims(config)
    with jax.default_matmul_precision("highest"):
        want = ref.whole_layer_ffn(h, lp, d, (0, 16)) + ref._swiglu(
            h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=1e-5)
    # and one share is what the reference gives for that share alone
    one = moe.routed_experts(
        h, idx, gates, lp["experts_gate"][4:8], lp["experts_up"][4:8],
        lp["experts_down"][4:8], experts_held=(4, 4), dtype=jnp.float32,
        tile_m=16)[0]
    np.testing.assert_allclose(
        np.asarray(one),
        np.asarray(ref.whole_layer_ffn(h, jax.tree.map(
            lambda a: a[4:8] if a.shape[:1] == (16,) and a.ndim == 3 else a,
            lp), d, (4, 4))), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("case", ["every_token_chooses_held_experts",
                                  "every_token_chooses_one_held_expert"])
def test_no_row_is_dropped_whatever_the_routing(case):
    """No capacity factor: with every token's choices inside the held
    experts the row bound is REACHED and every row is computed; with every
    token also choosing one and the same expert that expert holds a row of
    every token."""
    config = _f32()
    first, count = 4, 4
    k, tokens = config.num_experts_per_tok, 64
    lp = jax.tree.map(
        lambda a: a[0], lm.init_params(config, jax.random.PRNGKey(7))
        ["layers"]["seg01"]["0"])
    bias = jnp.zeros(16).at[first:first + count].set(5.0)
    if case == "every_token_chooses_one_held_expert":
        bias = bias.at[first + 1].set(9.0)
    h = jax.random.normal(jax.random.PRNGKey(8), (tokens, config.hidden_size))
    idx, gates = moe.sigmoid_route(h, lp["router_w"], bias,
                                   num_experts_per_token=k,
                                   scale=config.routed_scaling_factor)
    assert bool(jnp.all((idx >= first) & (idx < first + count)))
    y, stats = moe.routed_experts(
        h, idx, gates, lp["experts_gate"], lp["experts_up"],
        lp["experts_down"], experts_held=(first, count), dtype=jnp.float32,
        tile_m=16)
    assert int(stats["rows_held"]) == tokens * k == int(stats["rows_bound"])
    if case == "every_token_chooses_one_held_expert":
        assert int(stats["load_max"]) == tokens
    want = ref.whole_layer_ffn(
        h, {**lp, "router_bias": bias},
        {**_dims(config), "router_width": 16}, (first, count))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("usual_rows,side", [(None, "the bound's buffer"),
                                             (64, "the usual buffer"),
                                             (8, "over the usual: the bound's")])
def test_both_buffer_sizes_compute_every_row(usual_rows, side):
    """`usual_rows`: a step whose rows fit takes the shorter buffer, one
    whose rows do not takes the bound's; values and gradients are the same
    either way, and equal the reference's."""
    config = _f32()
    lp = jax.tree.map(
        lambda a: a[0], lm.init_params(config, jax.random.PRNGKey(13))
        ["layers"]["seg01"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(14), (48, config.hidden_size))
    idx, gates = moe.sigmoid_route(
        h, lp["router_w"], lp["router_bias"],
        num_experts_per_token=config.num_experts_per_tok,
        scale=config.routed_scaling_factor)
    weights = (lp["experts_gate"], lp["experts_up"], lp["experts_down"])

    def layer(h, gates, weights, usual_rows):
        return moe.routed_experts(
            h, idx, gates, *weights, experts_held=(0, 4), dtype=jnp.float32,
            tile_m=16, usual_rows=usual_rows)

    y, stats = layer(h, gates, weights, usual_rows)
    held = int(stats["rows_held"])
    assert 8 < held <= 64 < int(stats["rows_bound"]) == 48 * 3
    want = ref.whole_layer_ffn(h, lp, _dims(config), (0, 4))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(15), y.shape)
    grads = [jax.grad(lambda h, g, ws: jnp.sum(layer(h, g, ws, u)[0] * w),
                      argnums=(0, 1, 2))(h, gates, weights)
             for u in (usual_rows, None)]
    for got, base in zip(jax.tree.leaves(grads[0]),
                         jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   atol=1e-5, rtol=1e-5)


def _held_share_case(dtype, tokens=40, width=128, inner=64, k=3):
    """16 experts, this chip holds 4..7: token 0 has ALL its k experts held,
    expert 7 holds no row, some tokens have none held.  A width the kernel
    takes (a whole lane tile)."""
    rng = np.random.default_rng(21)
    idx = np.stack([rng.permutation(16)[:k] for _ in range(tokens)])
    idx[idx == 7] = 12
    idx[0] = [6, 4, 5]
    idx[1] = [0, 1, 2]
    ks = jax.random.split(jax.random.PRNGKey(22), 5)
    h = jax.random.normal(ks[0], (tokens, width), dtype)
    gates = jax.random.uniform(ks[1], (tokens, k), jnp.float32, 0.1, 1.0)
    weights = (jax.random.normal(ks[2], (4, width, inner), dtype) / 8,
               jax.random.normal(ks[3], (4, width, inner), dtype) / 8,
               jax.random.normal(ks[4], (4, inner, width), dtype) / 8)
    return h, jnp.asarray(idx, jnp.int32), gates, weights


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("usual_rows,side", [(64, "the usual buffer"),
                                             (8, "the bound's buffer")])
def test_the_movers_by_index_equal_the_gathers(usual_rows, side, dtype,
                                               monkeypatch):
    """`routed_experts`' value, dx, dw and d_gates with the rows moved by
    ops/row_gather.py's kernel (interpreted) equal those with XLA's gathers,
    to the dtype's rounding, on both sides of the `lax.cond`."""
    from ray_tpu.ops import row_gather

    h, idx, gates, weights = _held_share_case(dtype)
    mix = jax.random.normal(jax.random.PRNGKey(23), h.shape, jnp.float32)

    def layer(h, gates, weights):
        y, stats = moe.routed_experts(
            h, idx, gates, *weights, experts_held=(4, 4), dtype=dtype,
            tile_m=16, usual_rows=usual_rows)
        return jnp.sum(y.astype(jnp.float32) * mix), (y, stats)

    def run():
        monkeypatch.setattr(moe.dispatch, "_taken", {})
        (_, (y, stats)), grads = jax.value_and_grad(
            layer, argnums=(0, 1, 2), has_aux=True)(h, gates, weights)
        return y, stats, grads, moe.dispatch.taken()

    y, stats, grads, taken = run()
    assert set(taken["routed_experts"]) == {"interpret"}
    assert set(taken["routed_experts.plan"]) == {
        "rows_by_index,slots120,buffer192,entries<=120",
        f"rows_by_index,slots120,buffer{(-(-usual_rows // 16) + 4) * 16},"
        f"entries<={usual_rows}"}
    held = int(stats["rows_held"])
    assert (held <= usual_rows) == (side == "the usual buffer"), held
    monkeypatch.setattr(row_gather, "_use_pallas", lambda *a: False)
    y_x, _, grads_x, taken = run()
    assert set(taken["routed_experts"]) == {"xla"}
    assert all(p.startswith("rows_by_gather,") for p in
               taken["routed_experts.plan"])
    tol = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=2 ** -6, rtol=2 ** -6)
    for got, want in zip(jax.tree.leaves((y, grads)),
                         jax.tree.leaves((y_x, grads_x))):
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   np.asarray(want.astype(jnp.float32)),
                                   **tol)
    # the gate of an assignment held elsewhere gets nothing; token 0's three
    # all get something; the expert with no row gets no dw
    d_gates = np.asarray(grads[1])
    here = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8)
    assert not d_gates[~here].any() and d_gates[0].all()
    assert not np.asarray(grads[2][0].astype(jnp.float32))[3].any()


def test_the_bias_selects_and_is_not_in_the_gate():
    config = _f32()
    w = jax.random.normal(jax.random.PRNGKey(9), (config.hidden_size, 16))
    h = jax.random.normal(jax.random.PRNGKey(10), (32, config.hidden_size))
    bias = jnp.zeros(16).at[3].set(10.0)
    idx, gates = moe.sigmoid_route(h, w, bias, num_experts_per_token=3,
                                   scale=2.448)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.448, rtol=1e-6)
    scores = jax.nn.sigmoid(h @ w)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(picked / picked.sum(-1, keepdims=True) * 2.448), rtol=1e-5)
    grad = jax.grad(lambda b: moe.sigmoid_route(
        h, w, b, num_experts_per_token=3, scale=2.448)[1].sum())(bias)
    assert not np.asarray(grad).any()


def test_the_probe_runs_the_routed_layer_alone_on_the_references_operands():
    config = _f32()
    params = lm.init_params(config, jax.random.PRNGKey(11))
    run = ref.Pass(params, _tokens()[0, :-1], _dims(config))
    operands, want = run.routed_experts()
    got = lm.routed_experts(*operands, config=config)
    assert got.shape == want.shape == (1, 128, config.hidden_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # in the model's own dtype the rows are bfloat16: close, not equal
    low = lm.routed_experts(*operands, config=lm.LatentMoEConfig.tiny())
    err = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2


def test_train_step_carries_the_counts_and_leaves_the_bias(monkeypatch):
    """Through ShardedTrainStep: the step's metrics hold the last expert
    layer's routing counts, its first span holds them as attributes (what
    timeline.json keeps), AdamW's weight decay does not move the selection
    bias, and every other leaf moves."""
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer
    from ray_tpu.util import tracing

    config = lm.LatentMoEConfig.tiny(fused_ce=True)
    mesh = build_mesh(axes={"fsdp": 1}, devices=jax.devices()[:1])
    ts = ShardedTrainStep(config, mesh, optimizer=default_optimizer(
        warmup_steps=1, total_steps=10, mu_dtype=jnp.bfloat16,
        nu_dtype=jnp.bfloat16))
    state = ts.init(jax.random.PRNGKey(0))
    before = jax.tree.map(np.asarray, state["params"])
    batch = {"tokens": jnp.asarray(_tokens())}
    for _ in range(5):
        state, metrics = ts.step(state, batch)
    tokens = 2 * 128
    assert int(metrics["moe_rows_bound"]) == tokens * 3
    assert 0 < int(metrics["moe_rows_held"]) <= tokens * 3
    assert float(metrics["moe_load_mean"]) == int(metrics["moe_rows_held"]) / 4
    assert int(metrics["moe_load_max"]) >= float(metrics["moe_load_mean"])
    assert int(metrics["moe_rows_held_all_layers"]) >= int(
        metrics["moe_rows_held"])       # two expert layers' against the last's
    # steps 1, 2 and 4 are recorded whatever the tracing flag says, with
    # the counts; 3 and 5 are not
    spans = [s for s in tracing.get_spans(("train.",))
             if s["name"] == "train.step"][-3:]
    assert [s["attributes"]["step"] for s in spans] == [1, 2, 4]
    for s in spans:
        assert s["attributes"]["moe_rows_bound"] == tokens * 3
        assert {"moe_load_max", "moe_load_mean", "moe_rows_held",
                "moe_rows_held_all_layers"} <= set(s["attributes"])
    after = jax.tree.map(np.asarray, state["params"])
    frozen = lm.not_trained(config)
    moved = jax.tree.map(lambda a, b: bool((a != b).any()), before, after)
    for (path, is_frozen), (_, has_moved) in zip(
            jax.tree_util.tree_flatten_with_path(frozen)[0],
            jax.tree_util.tree_flatten_with_path(moved)[0]):
        assert has_moved != is_frozen, path
    assert sum(jax.tree.leaves(frozen)) == 1    # one stacked leaf: the bias


def test_layout_names_and_count():
    config = lm.LatentMoEConfig.tiny()
    params = jax.eval_shape(lambda: lm.init_params(config,
                                                   jax.random.PRNGKey(0)))
    assert sorted(params) == ["final_norm_w", "layers", "lm_head",
                              "tok_embed"]
    assert sorted(params["layers"]) == ["seg00", "seg01"]
    assert params["layers"]["seg00"]["0"]["w_gate"].shape == (1, 64, 128)
    assert params["layers"]["seg01"]["0"]["experts_gate"].shape == (
        2, 4, 64, 32)
    assert params["layers"]["seg01"]["0"]["router_w"].shape == (2, 64, 16)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == lm.num_params(config)
    axes = lm.logical_axes(config)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    assert lm.LatentMoEConfig().qk_head_dim == 192


@pytest.mark.parametrize("bad", [
    {"hc_mult": 8}, {"scoring_func": "softmax"}, {"n_group": 8},
    {"tie_word_embeddings": True}, {"rope_interleave": False},
    {"first_held_expert": 14}, {"rope_scaling": {"type": "linear",
                                                 "factor": 4}},
    {"num_nextn_predict_layers": 2}])
def test_what_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        lm.LatentMoEConfig.tiny(**bad)


def test_rope_turns_interleaved_pairs():
    config = lm.LatentMoEConfig.tiny()
    cos, sin = lm.rope_tables(8, config)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 8, 2, 16))
    got = lm.rope_interleaved(x, cos, sin)
    want = ref._rope(x[0], config.rope_theta)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(x[0, 0]),
                               atol=1e-6)     # position 0 is not turned
    assert gm.TILE_M % 16 == 0


def _attention_roped_in_xla(u, lp, cos, sin, c):
    """Latent attention as the model computed it before its operands went to
    the kernels in parts: the published pairing's rope (`rope_interleaved`)
    on q_pe and k_pe in XLA, k_pe broadcast to the heads, q and k
    concatenated, [k_nope | v] split, then the whole-operand kernels."""
    from ray_tpu.ops.attention import flash_attention

    b, s, _ = u.shape
    heads, nope, rope = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim)
    rank, dv = c.kv_lora_rank, c.v_head_dim
    q = lm._matmul(u, lp["wq"], c).reshape(b, s, heads, nope + rope)
    latent = lm._matmul(u, lp["wkv_a"], c)
    c_kv = lm.rms_norm(latent[..., :rank], lp["kv_norm_w"], c.rms_norm_eps)
    kv = lm._matmul(c_kv, lp["wkv_b"], c).reshape(b, s, heads, nope + dv)
    k_pe = lm.rope_interleaved(latent[..., None, rank:], cos, sin)
    q = jnp.concatenate(
        [q[..., :nope], lm.rope_interleaved(q[..., nope:], cos, sin)],
        axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))],
        axis=-1)
    a = flash_attention(q, k, kv[..., nope:], causal=True,
                        sm_scale=1.0 / (nope + rope) ** 0.5)
    return lm._matmul(a.reshape(b, s, heads * dv), lp["wo"], c)


def test_parts_path_is_rope_in_xla_on_the_published_columns(monkeypatch):
    """A parameter tree from `init_params`, columns as published, gives the
    same loss and the same gradients (W_q's and W_kva's, whose rotary
    columns the program reorders at use, first) through the kernels that
    take the parts as through rope in XLA with the interleaved pairing: the
    reordering, its transpose on the gradients and the kernels' half-split
    rope are the published rope."""
    from ray_tpu.ops import dispatch

    config = _f32()
    params = lm.init_params(config, jax.random.PRNGKey(8))
    batch = {"tokens": jnp.asarray(_tokens(seed=9))}

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(
            lambda p: lm.loss_fn(p, batch, config)))(params)

    monkeypatch.setattr(dispatch, "_taken", {})
    loss, grads = loss_and_grads()
    plans = list(dispatch.taken()["flash_attention.plan"])
    assert plans and all(p.endswith(
        ",dqk48,dv32,latent_parts,rope_in_kernel16of48") for p in plans)
    monkeypatch.setattr(lm, "_attention", _attention_roped_in_xla)
    stack.layer_fn.cache_clear()    # a traced layer is cached by its function
    monkeypatch.setattr(dispatch, "_taken", {})
    want_loss, want = loss_and_grads()
    stack.layer_fn.cache_clear()
    assert not any("latent_parts" in p
                   for p in dispatch.taken()["flash_attention.plan"])
    assert abs(float(loss) - float(want_loss)) < 1e-5
    for seg in ("seg00", "seg01"):
        for name, w in want["layers"][seg]["0"].items():
            g = np.asarray(grads["layers"][seg]["0"][name])
            w = np.asarray(w)
            assert np.linalg.norm(g - w) <= 2e-4 * max(
                np.linalg.norm(w), 1e-12), (seg, name)
    # the rotary columns' gradients are not all alike: a reordering that
    # came back wrong would show
    rotary = np.asarray(want["layers"]["seg00"]["0"]["wkv_a"])[
        0, :, config.kv_lora_rank:]
    assert np.linalg.norm(rotary[:, 0::2] - rotary[:, 1::2]) \
        > 0.1 * np.linalg.norm(rotary)


def test_kanana_tiny_is_bit_equal_to_the_parents():
    """The one-lane form's loss, its gradient's absolute sum and its counts
    on seeded weights, as PR 51's tree computed them: the lanes, the query
    latent and the second tail leave that program's arithmetic alone."""
    config = lm.LatentMoEConfig.tiny()
    params = lm.init_params(config, jax.random.PRNGKey(11))
    batch = {"tokens": jnp.asarray(np.asarray(jax.random.randint(
        jax.random.PRNGKey(12), (2, 129), 0, 256)))}
    loss, metrics = jax.jit(
        lambda p, b: lm.loss_and_metrics(p, b, config))(params, batch)
    grads = jax.jit(jax.grad(lambda p, b: lm.loss_fn(p, b, config)))(
        params, batch)
    assert float(loss).hex() == "0x1.8227d40000000p+2"
    assert float(sum(jnp.sum(jnp.abs(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(grads))).hex() \
        == "0x1.88ab140000000p+9"
    assert {k: float(v) for k, v in metrics.items()} == {
        "moe_load_max": 96.0, "moe_load_mean": 63.0, "moe_rows_bound": 768.0,
        "moe_rows_held": 252.0, "moe_rows_held_all_layers": 452.0}
