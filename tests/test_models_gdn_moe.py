"""models/gdn_moe.py (three gated-delta-rule layers to one gated full layer
with a rotary quarter, per-head q / k norms, zero-centred norms, a per-
element gate, softmax-routed experts beside a GATED shared expert) at tiny
widths, kernels interpreted on the CPU, against the benchmark's plain
reference (benchmark/reference/qwen3_next_gdn_moe.py) on seeded weights."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import qwen3_next_gdn_moe as ref
from ray_tpu.models import common, gdn_moe as gm


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


# The program's per-token loss as ONE jitted function for the file: cases
# with an equal configuration share its compile, where a call dispatched
# primitive by primitive compiles every layer scan anew.  (The init stays
# eager: a leaf's draw is cached by its shape across cases and configurations,
# which one jitted init a configuration is not.)
_nll = jax.jit(gm.token_nll, static_argnums=2)


def _f32(**kw):
    return gm.GdnMoEConfig.tiny(dtype=jnp.float32, remat=False, **kw)


def _dims(config):
    """From the config as a configuration file's `model` group holds it."""
    return ref.dims_from_config({f.name: getattr(config, f.name)
                                 for f in dataclasses.fields(config)})


def _tokens(rows=2, seq=96, vocab=256, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (rows, seq + 1), 0, vocab))


def test_the_tiny_size_has_what_the_cell_has():
    """Both kinds of layer in the published 3 : 1 order in two scanned
    segments, 2 key / 4 value heads, group 2 in the full layer, a rotary
    quarter, 4 of 16 experts held; the published size's count."""
    config = gm.GdnMoEConfig.tiny()
    assert config.layer_types == (gm.LINEAR,) * 3 + (gm.FULL,)
    assert gm.segments(config) == [(gm.LINEAR, 0, 3), (gm.FULL, 3, 1)]
    assert config.rotary_width * 4 == config.head_dim
    assert config.num_attention_heads // config.num_key_value_heads == 2
    assert config.linear_num_value_heads // config.linear_num_key_heads == 2
    assert config.experts_held == (0, 4) and config.router_width == 16
    params = gm.init_params(config, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) \
        == gm.num_params(config)
    # the cell's share: ISSUE 42's count, and the published model whole
    cell = gm.GdnMoEConfig(num_hidden_layers=4, num_experts=32,
                           router_width=512, vocab_size=18992)
    assert gm.num_params(cell) == 625_667_136
    whole = gm.GdnMoEConfig()
    assert whole.layer_types.count(gm.FULL) == 12 and whole.rotary_width == 64
    assert round(gm.num_params(whole) / 1e9, 1) == 79.7
    # drawn, not constants: a zero-centred weight near 0, a plain one near 1
    lin = params["layers"]["seg00"]["0"]
    assert 0.02 < float(jnp.std(lin["ln1_w"])) < 0.2
    assert abs(float(jnp.mean(lin["gn_w"])) - 1.0) < 0.1
    assert float(jnp.std(lin["A_log"])) > 0 and float(
        jnp.max(jax.nn.softplus(lin["dt_bias"]))) <= 0.1001


@pytest.mark.parametrize("fused_ce", [False, True])
def test_token_nll_matches_the_reference(fused_ce):
    config = _f32(fused_ce=fused_ce)
    params = gm.init_params(config, jax.random.PRNGKey(3))
    tokens = _tokens()
    got = _nll(params, {"tokens": jnp.asarray(tokens)}, config)
    want = ref.batch_token_nll(params, tokens, _dims(config))
    # the fused cross-entropy multiplies in bfloat16 whatever the model's
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2 if fused_ce else 3e-4)
    assert abs(float(got.mean()) - np.log(256)) < 1.0


def test_gradient_matches_the_reference_layer_by_layer():
    """jax.grad of the program's loss (the rule's backward kernel and the
    group sums behind it, the reverse running sum, the conv, the l2 and
    gated norms, the head-64 flash VJP under a quarter rope, the column
    reordering at use, the grouped kernels' and the gathers' VJPs, the
    shared expert's gate) against the reference's gradient walked back a
    layer at a time."""
    config = _f32()
    params = gm.init_params(config, jax.random.PRNGKey(4))
    tokens = _tokens(rows=1)
    got = jax.jit(jax.grad(lambda p: gm.loss_fn(
        p, {"tokens": jnp.asarray(tokens)}, config)))(params)
    run = ref.Pass(params, tokens[0, :-1], _dims(config), for_grads=True)
    seen = 0
    for path, grad in run.grads(tokens[0, 1:]):
        for name, w in (grad.items() if isinstance(grad, dict)
                        else [(None, grad)]):
            g = got[path[0]] if name is None \
                else got["layers"][path[1]][path[2]][name][path[3]]
            w = np.asarray(w)
            assert np.linalg.norm(np.asarray(g) - w) \
                <= 1e-3 * np.linalg.norm(w), (path, name)
            seen += 1
    # three at the top; a linear layer's 17 leaves, a full layer's 16
    assert seen == 3 + 3 * 17 + 16


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips, four of sixteen experts each (every `first_held_expert`):
    the routed parts that the program's layer gives for the four shares,
    plus the GATED shared expert counted ONCE, are what the uncut sixteen-
    expert reference layer gives."""
    config = _f32(num_experts=16, router_width=16)
    lp = jax.tree.map(
        lambda a: a[0], gm.init_params(config, jax.random.PRNGKey(5))
        ["layers"]["seg00"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(6), (96, config.hidden_size))
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        share = dataclasses.replace(config, num_experts=4,
                                    first_held_expert=first)
        part, stats = gm._routed_part(
            h, lp["router_w"], lp["experts_gate"][held],
            lp["experts_up"][held], lp["experts_down"][held], share)
        total, rows = total + part, rows + int(stats["rows_held"])
        # and a share is what the reference gives for that share alone
        alone = ref.whole_layer_ffn(h, jax.tree.map(
            lambda a: a[held] if a.shape[:1] == (16,) and a.ndim == 3 else a,
            lp), _dims(config), (first, 4), with_shared=False)
        np.testing.assert_allclose(np.asarray(part), np.asarray(alone),
                                   atol=2e-5, rtol=1e-5)
    assert rows == 96 * config.num_experts_per_tok      # every assignment
    with jax.default_matmul_precision("highest"):
        shared = common.swiglu(h, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"], jnp.float32)
        total = total + jax.nn.sigmoid(h @ lp["shared_expert_gate"]) * shared
        want = ref.whole_layer_ffn(h, lp, _dims(config), (0, 16))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=3e-5, rtol=1e-5)
    # the gate is felt: ungated, the layer is another
    assert float(jnp.abs(total - (want - (
        jax.nn.sigmoid(h @ lp["shared_expert_gate"]) - 1) * shared)).max()) \
        > 1e-3


@pytest.mark.parametrize("usual_rows,side", [(48, "the usual buffer"),
                                             (4, "the bound's buffer")])
def test_the_softmax_routed_share_by_index_equals_the_gathers(
        usual_rows, side, monkeypatch):
    """The cell's routing (softmax, renormalised top k) over a share of the
    experts, bfloat16 rows of a width the kernel takes: value and gradients
    with the rows moved by ops/row_gather.py's kernel (interpreted) equal
    those by XLA's gathers to bfloat16's rounding, on the named side of the
    `lax.cond`; a token whose k experts are all held, an expert with no row."""
    from ray_tpu.models import moe
    from ray_tpu.ops import row_gather

    tokens, width, inner, k = 32, 128, 64, 2
    ks = jax.random.split(jax.random.PRNGKey(31), 6)
    h = jax.random.normal(ks[0], (tokens, width), jnp.bfloat16)
    # eight experts, 2..4 held; expert 4 is never chosen, token 0 chooses 2, 3
    router_w = jax.random.normal(ks[1], (width, 8)) / 8
    router_w = router_w.at[:, 4].set(0.0)
    h = h.at[0].set((40 * (router_w[:, 2] + router_w[:, 3])
                     ).astype(jnp.bfloat16))
    weights = (jax.random.normal(ks[2], (3, width, inner), jnp.bfloat16) / 8,
               jax.random.normal(ks[3], (3, width, inner), jnp.bfloat16) / 8,
               jax.random.normal(ks[4], (3, inner, width), jnp.bfloat16) / 8)
    mix = jax.random.normal(ks[5], h.shape, jnp.float32)
    def route(h, router_w):
        probs = jax.nn.softmax(jnp.dot(
            h.astype(jnp.float32), router_w,
            precision=jax.lax.Precision.HIGHEST)
            - 1e3 * (jnp.arange(8) == 4), axis=-1)
        return moe.select_experts(probs, None, num_experts_per_token=k,
                                  gate_rule="renormalised")

    idx, _ = route(h, router_w)
    assert sorted(np.asarray(idx[0])) == [2, 3] and not bool(
        jnp.any(idx == 4))

    def layer(h, router_w, weights):
        _, gates = route(h, router_w)
        y, stats = moe.routed_experts(
            h, idx, gates, *weights, experts_held=(2, 3),
            dtype=jnp.bfloat16, tile_m=16, usual_rows=usual_rows)
        return jnp.sum(y.astype(jnp.float32) * mix), (y, stats)

    def run():
        (_, (y, stats)), grads = jax.value_and_grad(
            layer, argnums=(0, 1, 2), has_aux=True)(h, router_w, weights)
        return y, stats, grads

    y, stats, grads = run()
    held = int(stats["rows_held"])
    assert 4 < held <= 48
    assert (held <= usual_rows) == (side == "the usual buffer")
    assert row_gather.path(width, k) == "interpret"
    monkeypatch.setattr(row_gather, "_use_pallas", lambda *a: False)
    y_x, _, grads_x = run()
    for got, want in zip(jax.tree.leaves((y, grads)),
                         jax.tree.leaves((y_x, grads_x))):
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   np.asarray(want.astype(jnp.float32)),
                                   atol=2 ** -6, rtol=2 ** -6)
    # the expert nobody chose gets no dw; the router feels the held gates
    assert not np.asarray(grads[2][0].astype(jnp.float32))[2].any()
    assert np.asarray(grads[1]).any()


def test_the_quarter_rope_reaches_the_kernels_by_columns_and_a_tail():
    """`_rotary_first` on a head's published columns and `kernel_tables`:
    the kernels' whole-head turn (pair i with i + d/2) of the reordered
    head is the reference's turn of the first quarter, reordered."""
    from ray_tpu.ops.attention import rope_reference

    config = _f32()
    d, r = config.head_dim, config.rotary_width
    assert (d, r) == (64, 16)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 3, d))
    cos, sin = gm.kernel_tables(40, config)
    assert cos.shape == (40, d // 2)
    assert bool((cos[:, r // 2:] == 1).all() & (sin[:, r // 2:] == 0).all())
    got = rope_reference(gm._rotary_first(x, config), cos[None], sin[None])
    want = gm._rotary_first(
        ref._rope(x[0], r, float(config.rope_theta))[None], config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # a permutation of the columns, the same for every head
    order = np.asarray(gm._rotary_first(jnp.arange(d), config))
    assert sorted(order) == list(range(d))
    assert list(order[:r // 2]) == list(range(r // 2))
    assert list(order[d // 2:d // 2 + r // 2]) == list(range(r // 2, r))


def test_the_probe_runs_the_rule_alone_on_the_references_operands():
    config = _f32()
    params = gm.init_params(config, jax.random.PRNGKey(11))
    run = ref.Pass(params, _tokens()[0, :-1], _dims(config))
    operands, want = run.gated_delta_rule()
    assert operands[0].shape == (1, 96, 2, 32) and want.shape == (1, 96, 4, 32)
    got = gm.gated_delta_rule(*operands, config=config)
    assert got.dtype == jnp.float32
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 3e-5
    # with the operands as the mixer hands them over (bfloat16): close
    low = gm.gated_delta_rule(
        *(a.astype(jnp.bfloat16) for a in operands[:3]), *operands[3:],
        config=config)
    err = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2


def _the_parents_linear_mixer(u, lp, c):
    """`gdn_moe._linear_mixer` as PR 45 had it: the chain between W_qkvz and
    the rule written out in XLA (the conv's shifted adds and SiLU in the
    compute dtype, the l2 norms by whole tiles, v a slice), the group summed
    over the view behind the rule.  Kept here as the plain formulation."""
    import math

    b, s, _ = u.shape
    hk, dk = c.linear_num_key_heads, c.linear_key_head_dim
    hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
    wide = c.conv_channels
    w = lp["w_qkvz"].astype(c.dtype)
    qkv = gm._matmul(u, w[:, :wide], c)
    z = gm._matmul(u, w[:, wide:], c)
    ba = gm._matmul(u, lp["w_ba"], c, jnp.float32)
    qkv = jax.nn.silu(common.causal_depthwise_conv(qkv, lp["conv_w"]))

    def l2_normalised(x, scale=1.0):
        return gm._per_head(x, hk, lambda t: t * (scale * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + gm.L2_EPS)))

    q = l2_normalised(qkv[..., :hk * dk], 1.0 / math.sqrt(dk))
    k = l2_normalised(qkv[..., hk * dk:2 * hk * dk])
    v = qkv[..., 2 * hk * dk:]
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., hv:] + lp["dt_bias"].astype(jnp.float32))
    o = gm.gated_delta_rule(q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
                            v.reshape(b, s, hv, dv), g, beta, c)
    gn_w = lp["gn_w"].astype(jnp.float32)
    y = gm._per_head(o.reshape(b, s, hv * dv), hv,
                     lambda t: t * jax.lax.rsqrt(jnp.mean(
                         t * t, axis=-1, keepdims=True) + c.rms_norm_eps)
                     * gn_w)
    y = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(
        c.dtype)
    return gm._matmul(y, lp["wo"], c)


@pytest.mark.parametrize("width,path,remat", [
    (128, "interpret", False), (128, "interpret", True), (32, "xla", False)])
def test_the_linear_mixer_through_the_op_equals_the_parents_lines(
        width, path, remat, monkeypatch):
    """`_linear_mixer` through ops/mixer_chain.py (heads of 128: the kernels,
    interpreted, over two row tiles; heads of 32: XLA; under `remat` with
    q, k, v made again for the rule's backward) against the parent's lines
    behind the parent's norm, float32: the value and every leaf's gradient,
    the input's too."""
    from ray_tpu.ops import dispatch, gated_delta as gd

    config = gm.GdnMoEConfig.tiny(
        dtype=jnp.float32, remat=remat, linear_key_head_dim=width,
        linear_value_head_dim=width)
    params = gm.init_params(config, jax.random.PRNGKey(5))
    lp = jax.tree.map(lambda a: a[0], params["layers"]["seg00"]["0"])
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 32, config.hidden_size))
    weight = jax.random.normal(jax.random.PRNGKey(7), u.shape)

    def the_parents(x, lp, c):
        return _the_parents_linear_mixer(gm.zero_centred_norm(
            x, lp["ln1_w"], c.rms_norm_eps), lp, c)

    def loss(mixer):
        return lambda u, lp: jnp.sum(mixer(u, lp, config) * weight)

    monkeypatch.setattr(dispatch, "_taken", {})
    got = gm._linear_mixer(u, lp, config)
    got_g = jax.jit(jax.grad(loss(gm._linear_mixer), argnums=(0, 1)))(u, lp)
    assert set(dispatch.taken()["mixer_chain"]) == {path}
    # the parent's sum over the group's view, too
    monkeypatch.setattr(gd, "over_group", lambda d, hv, hk: jnp.sum(
        d.reshape(*d.shape[:2], hk, hv // hk, -1), axis=3).reshape(
            *d.shape[:2], -1))
    want = the_parents(u, lp, config)
    want_g = jax.jit(jax.grad(loss(the_parents), argnums=(0, 1)))(u, lp)
    assert set(got_g[1]) == set(lp) == set(gm._layer_shapes(gm.LINEAR,
                                                            config))
    for name, g, w in [("the value", got, want), ("u", got_g[0], want_g[0])
                       ] + [(n, got_g[1][n], want_g[1][n]) for n in lp]:
        # the mixer's eight leaves; the layer's second norm and its experts
        # lie outside it
        assert (float(jnp.linalg.norm(w)) > 0) == (name in (
            "the value", "u", "ln1_w", "w_qkvz", "w_ba", "conv_w", "A_log",
            "dt_bias", "gn_w", "wo")), name
        assert float(jnp.linalg.norm(g - w)) <= 1e-4 * float(
            jnp.linalg.norm(w)), name


def test_train_step_carries_the_counts_and_the_plans(monkeypatch):
    """Through ShardedTrainStep: the loss falls, the step's metrics hold the
    LAST layer's routing counts and the rows of all four layers, its forced
    spans hold them as attributes, and the plans say what ran."""
    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer
    from ray_tpu.util import tracing

    # what ran HERE: the record is the process's, and `--dist loadfile` puts
    # other files' interpreted chains in front of this one
    monkeypatch.setattr(dispatch, "_taken", {})
    config = gm.GdnMoEConfig.tiny(fused_ce=True)
    mesh = build_mesh(axes={"fsdp": 1}, devices=jax.devices()[:1])
    ts = ShardedTrainStep(config, mesh, optimizer=default_optimizer(
        warmup_steps=1, total_steps=10, mu_dtype=jnp.bfloat16,
        nu_dtype=jnp.bfloat16))
    state = ts.init(jax.random.PRNGKey(0))
    before = jax.tree.map(np.asarray, state["params"])
    batch = {"tokens": jnp.asarray(_tokens())}
    losses = []
    for _ in range(3):      # the first step's rate is the warm-up's zero
        state, metrics = ts.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    tokens = 2 * 96
    assert int(metrics["moe_rows_bound"]) == tokens * 3
    assert 0 < int(metrics["moe_rows_held"]) <= tokens * 3
    assert int(metrics["moe_rows_held_all_layers"]) > int(
        metrics["moe_rows_held"])       # four layers' against one's
    spans = [s for s in tracing.get_spans(("train.",))
             if s["name"] == "train.step"][-2:]    # steps 1, 2, 4, ..
    assert [s["attributes"]["step"] for s in spans] == [1, 2]
    assert {"moe_load_max", "moe_load_mean", "moe_rows_held",
            "moe_rows_held_all_layers", "moe_rows_bound",
            "remat"} <= set(spans[0]["attributes"])
    after = jax.tree.map(np.asarray, state["params"])
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a != b).any()), before, after)))
    taken = dispatch.taken()
    assert any(p.startswith("chunk64,heads4over2,dk32,dv32,state_f32,"
                            "bwd_pallas,passes")
               for p in taken["gated_delta_rule.plan"])
    assert set(taken["gated_delta_rule"]) == {"interpret"}
    # heads of 32: the chain between W_qkvz and the rule stays XLA's
    assert set(taken["mixer_chain"]) == {"xla"}
    assert any(",rope_in_kernel,operands_bshd,heads2x64" in p
               for p in taken["flash_attention.plan"])
    assert "full_attention:in_kernel16of64_columns_reordered_at_use_" \
        "identity_tail" in taken["gdn_moe.rope"]
    assert any(p.startswith("kept:") for p in taken["train.remat"])


def test_layout_names_and_scopes():
    """`layers/<segment>/0/<leaf>` with a leading axis of repeats (what the
    benchmark's driver reads), the logical axes beside every leaf, and the
    linear mixer under `ssm`, the full layer under `attn.full` with its
    gate under `attn.gate`."""
    config = gm.GdnMoEConfig.tiny()
    shapes = jax.eval_shape(lambda: gm.init_params(config,
                                                   jax.random.PRNGKey(0)))
    axes = gm.logical_axes(config)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    lin, full = (shapes["layers"][s]["0"] for s in ("seg00", "seg01"))
    assert lin["w_qkvz"].shape == (3, 64, 2 * 64 + 2 * 128)
    assert lin["conv_w"].shape == (3, 4, 256) and lin["A_log"].shape == (3, 4)
    assert full["wq"].shape == (1, 64, 4 * 2 * 64)
    assert full["shared_expert_gate"].shape == (1, 64, 1)
    tokens = jnp.asarray(_tokens(rows=1))
    text = jax.jit(lambda p: gm.loss_fn(p, {"tokens": tokens}, config)
                   ).lower(shapes).as_text(debug_info=True)
    for scope in (common.SSM, common.ATTN_FULL, common.ATTN_GATE, common.MLP,
                  common.MOE_ROUTE, common.MOE_EXPERTS, common.LOSS):
        assert f"/{scope}/" in text, scope
    assert common.ATTN_SLIDING not in text


@pytest.mark.parametrize("bad", [
    {"rope_scaling": {"factor": 2}}, {"mlp_only_layers": (0,)},
    {"norm_topk_prob": False}, {"tie_word_embeddings": True},
    {"use_sliding_window": True}, {"partial_rotary_factor": 0.3},
    {"first_held_expert": 14}])
def test_what_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        gm.GdnMoEConfig.tiny(**bad)
