"""models/swa_moe.py (windowed and full grouped-query layers of different
head counts, per-head gates, two rope tables, softmax-routed experts) at tiny
widths, kernels interpreted on the CPU, against the benchmark's plain
reference (benchmark/reference/laguna_swa_moe.py) on seeded weights."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import laguna_swa_moe as ref
from ray_tpu.models import common, moe, swa_moe as sm


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


# The program's per-token loss as ONE jitted function for the file: cases
# with an equal configuration share its compile, where a call dispatched
# primitive by primitive compiles every layer scan anew.  (The init stays
# eager: a leaf's draw is cached by its shape across cases and configurations,
# which one jitted init a configuration is not.)
_nll = jax.jit(sm.token_nll, static_argnums=2)


def _f32(**kw):
    return sm.SwaMoEConfig.tiny(dtype=jnp.float32, remat=False, **kw)


def _model_group(config):
    """The config as a configuration file's `model` group would hold it."""
    group = {f.name: getattr(config, f.name)
             for f in dataclasses.fields(config)}
    return {**group, "rope_parameters": config.rope}


def _dims(config):
    return ref.dims_from_config(_model_group(config))


def _tokens(rows=2, seq=128, vocab=256, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (rows, seq + 1), 0, vocab))


def test_the_tiny_size_has_what_the_cell_has():
    """Both kinds of layer in the published order, two head counts, groups
    of 2 and 3, a window shorter than the sequence, 4 of 16 experts held;
    three segments, the sliding run scanned."""
    config = sm.SwaMoEConfig.tiny()
    assert config.layer_kinds == (
        (sm.FULL, 4, sm.DENSE), (sm.SLIDING, 6, sm.SPARSE),
        (sm.SLIDING, 6, sm.SPARSE), (sm.SLIDING, 6, sm.SPARSE),
        (sm.FULL, 4, sm.SPARSE))
    assert [(first, repeats) for _, first, repeats in sm.segments(config)] \
        == [(0, 1), (1, 3), (4, 1)]
    assert config.sliding_window < 128 and config.experts_held == (0, 4)
    assert config.rotary_width(sm.FULL) == 32 \
        and config.rotary_width(sm.SLIDING) == 64
    # the published pattern, whole: a full layer, (3 sliding + 1 full) x 11,
    # 3 sliding; 48 / 72 heads by kind come from the file, not from here
    whole = sm.SwaMoEConfig()
    assert len(whole.layer_types) == 48 \
        and whole.layer_types.count(sm.FULL) == 12
    assert len(sm.segments(whole)) == 1 + 2 * 11 + 1


@pytest.mark.parametrize("fused_ce", [False, True])
def test_token_nll_matches_the_reference(fused_ce):
    config = _f32(fused_ce=fused_ce)
    params = sm.init_params(config, jax.random.PRNGKey(3))
    tokens = _tokens()
    got = _nll(params, {"tokens": jnp.asarray(tokens)}, config)
    want = ref.batch_token_nll(params, tokens, _dims(config))
    # the fused cross-entropy multiplies in bfloat16 whatever the model's
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2 if fused_ce else 2e-4)
    assert abs(float(got.mean()) - np.log(256)) < 1.0


def test_gradient_matches_the_reference_layer_by_layer():
    """jax.grad of the program's loss (the flash kernels' VJPs under a
    window and under each rope, GQA's repeat, the column reordering at use,
    the grouped kernels' and the gathers' VJPs) against the reference's
    gradient walked back a layer at a time."""
    config = _f32()
    params = sm.init_params(config, jax.random.PRNGKey(4))
    tokens = _tokens()
    got = jax.jit(jax.grad(lambda p: sm.loss_fn(
        p, {"tokens": jnp.asarray(tokens)}, config)))(params)
    want = {}
    for row in tokens:
        run = ref.Pass(params, row[:-1], _dims(config), for_grads=True)
        for path, grad in run.grads(row[1:]):
            for name, g in (grad.items() if isinstance(grad, dict)
                            else [(None, grad)]):
                want[path, name] = want.get((path, name), 0) \
                    + np.asarray(g) / len(tokens)
    for (path, name), w in want.items():
        g = got[path[0]] if name is None \
            else got["layers"][path[1]][path[2]][name][path[3]]
        assert np.linalg.norm(np.asarray(g) - w) <= 2e-4 * np.linalg.norm(w), \
            (path, name)
    # three at the top, the dense layer's ten, four expert layers' fourteen
    assert len(want) == 3 + 10 + 4 * 14


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips, four of sixteen experts each (every `first_held_expert`):
    the routed parts that the program's layer gives for the four shares,
    plus the shared expert counted ONCE, are what the uncut sixteen-expert
    reference layer gives."""
    config = _f32(num_experts=16, router_width=16)
    lp = jax.tree.map(
        lambda a: a[0], sm.init_params(config, jax.random.PRNGKey(5))
        ["layers"]["seg01"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(6), (96, config.hidden_size))
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        share = dataclasses.replace(config, num_experts=4,
                                    first_held_expert=first)
        part, stats = sm._routed_part(
            h, lp["router_w"], lp["experts_gate"][held],
            lp["experts_up"][held], lp["experts_down"][held], share)
        total, rows = total + part, rows + int(stats["rows_held"])
        # and a share is what the reference gives for that share alone
        alone = ref.whole_layer_ffn(h, jax.tree.map(
            lambda a: a[held] if a.shape[:1] == (16,) and a.ndim == 3 else a,
            lp), _dims(config), (first, 4))
        np.testing.assert_allclose(np.asarray(part), np.asarray(alone),
                                   atol=2e-5, rtol=1e-5)
    assert rows == 96 * config.num_experts_per_tok      # every assignment
    total = total + common.swiglu(h, lp["shared_gate"], lp["shared_up"],
                                  lp["shared_down"], jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.whole_layer_ffn(h, lp, _dims(config), (0, 16)) \
            + ref._swiglu(h, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_softmax_route_is_a_plain_top_k_of_a_float32_softmax():
    w = jax.random.normal(jax.random.PRNGKey(9), (64, 16))
    h = jax.random.normal(jax.random.PRNGKey(10), (32, 64))
    # ties: two pairs of experts with the same column score the same
    w = w.at[:, 5].set(w[:, 2]).at[:, 11].set(w[:, 7])
    idx, gates = moe.softmax_route(h, w, num_experts_per_token=3, scale=2.5)
    assert idx.dtype == jnp.int32 and gates.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        probs = np.asarray(jax.nn.softmax(h @ w, axis=-1), np.float64)
    # a plain top k: the largest first, the lower index first among equals
    order = np.lexsort((np.arange(16)[None, :].repeat(32, 0), -probs),
                       axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(idx), order)
    picked = np.take_along_axis(probs, order, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates), picked / picked.sum(-1, keepdims=True) * 2.5,
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-6)
    tied = np.asarray(idx)
    assert ((tied == 5).any(-1) <= (tied == 2).any(-1)).all()
    # bfloat16 inputs are scored in float32 all the same
    low, _ = moe.softmax_route(h.astype(jnp.bfloat16).astype(jnp.float32), w,
                               num_experts_per_token=3, scale=2.5)
    again, _ = moe.softmax_route(h.astype(jnp.bfloat16), w,
                                 num_experts_per_token=3, scale=2.5)
    np.testing.assert_array_equal(np.asarray(low), np.asarray(again))


def test_sigmoid_route_is_what_it_was_at_the_latent_cells_shapes():
    """The sigmoid router beside the new one, at the other expert cell's
    widths (hidden 2048, 128 experts, 6 a token, scale 2.448): bit for bit
    the formula as PR 34 wrote it, written out here."""
    h = jax.random.normal(jax.random.PRNGKey(20), (64, 2048))
    w = jax.random.normal(jax.random.PRNGKey(21), (2048, 128)) / math.sqrt(
        2048)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(22), (128,))
    idx, gates = moe.sigmoid_route(h, w, bias, num_experts_per_token=6,
                                   scale=2.448)
    scores = jax.nn.sigmoid(jnp.dot(h, w,
                                    precision=jax.lax.Precision.HIGHEST))
    _, want_idx = jax.lax.top_k(scores + bias, 6)
    picked = jnp.take_along_axis(scores, want_idx, axis=-1)
    want = picked / jnp.sum(picked, axis=-1, keepdims=True) * 2.448
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(want))


def test_yarn_tables_at_two_positions_worked_by_hand():
    """The full layers' table at the published settings (base 500,000,
    rotary width 64, factor 128, original length 8192, beta 32 / 1,
    attention factor 1.4852...), against a direct transcription and against
    numbers worked by hand: low = floor(64 ln(8192 / (32 x 2 pi)) / (2 ln
    500000)) = floor(9.04) = 9, high = ceil(64 ln(8192 / (2 pi)) / (2 ln
    500000)) = ceil(17.49) = 18, so frequencies 0..9 are as published,
    18..31 are divided by 128, and 10..17 lie on the ramp (i - 9) / 9."""
    rope = sm.PUBLISHED_ROPE[sm.FULL]
    inv = np.asarray(sm.yarn_inv_freq(rope, 64), np.float64)
    base = 500000.0
    low = math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(64 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (9, 18)
    want = []
    for i in range(32):
        extrap = base ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extrap / 128 * ramp + extrap * (1 - ramp))
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    # by hand: i = 4 is under the ramp (500000^(-1/8) = 0.19392), i = 12 a
    # third up it (500000^(-3/8) = 0.0072925; x (1/128 x 1/3 + 2/3)), i =
    # 20 past it (500000^(-5/8) / 128 = 2.1425e-6)
    np.testing.assert_allclose(inv[4], 0.193921, rtol=1e-5)
    np.testing.assert_allclose(inv[12], 0.0072925 * (1 / 384 + 2 / 3),
                               rtol=1e-4)
    np.testing.assert_allclose(inv[20], 2.1425e-6, rtol=1e-4)
    assert abs(0.1 * math.log(128) + 1 - rope["attention_factor"]) < 1e-12
    # the tables at positions 1 and 4097: cos and sin of position x
    # frequency, both times the attention factor
    config = sm.SwaMoEConfig(num_hidden_layers=1)
    cos, sin = sm.rope_tables(4098, sm.FULL, config)
    assert cos.shape == (4098, 32)
    f = rope["attention_factor"]
    for pos in (1, 4097):
        np.testing.assert_allclose(np.asarray(cos[pos]),
                                   f * np.cos(pos * inv), atol=2e-3)
        np.testing.assert_allclose(np.asarray(sin[pos]),
                                   f * np.sin(pos * inv), atol=2e-3)
    np.testing.assert_allclose(float(cos[1, 4]), f * math.cos(0.193921),
                               rtol=1e-5)           # 1.4573
    np.testing.assert_allclose(float(sin[4097, 20]),
                               f * math.sin(4097 * 2.1425e-6), rtol=1e-3)
    # the sliding layers': the default table over all 128 dimensions
    cos, _ = sm.rope_tables(3, sm.SLIDING, config)
    np.testing.assert_allclose(
        np.asarray(cos[2]), np.cos(2 * 10000.0 ** (-np.arange(64) / 64)),
        atol=1e-6)
    # and the reference's own transcription agrees
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(rope, 64)), inv,
                               rtol=1e-6)


def test_the_half_rope_reaches_the_kernels_by_columns_and_an_identity_tail():
    """W_q's columns reordered at use and tables with cos 1 / sin 0 behind
    the rotary pairs: the kernels' whole-head turn of the reordered head is
    the published partial turn of the head as published, reordered."""
    from ray_tpu.ops.attention import rope_reference

    config = _f32()
    d, heads, seq = config.head_dim, 4, 8
    w = jax.random.normal(jax.random.PRNGKey(30), (config.hidden_size,
                                                   heads * d))
    u = jax.random.normal(jax.random.PRNGKey(31), (seq, config.hidden_size))
    published = ref._rope((u @ w).reshape(seq, heads, d),
                          dict(config.rope[sm.FULL]))
    reordered = (u @ sm._rotary_first_halves(w, heads, config, sm.FULL)
                 ).reshape(1, seq, heads, d)
    cos, sin = sm.kernel_tables(seq, sm.FULL, config)
    assert cos.shape == (seq, d // 2)
    assert bool((cos[:, d // 4:] == 1).all() & (sin[:, d // 4:] == 0).all())
    got = rope_reference(reordered, cos[None], sin[None])[0]
    want = published.reshape(seq, heads, 2, 2, d // 4).swapaxes(2, 3)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.reshape(seq, heads, d)),
                               atol=1e-5)
    # a sliding layer's weights go as they are
    assert sm._rotary_first_halves(w, heads, config, sm.SLIDING) is w


def test_the_probe_runs_the_routed_layer_alone_on_the_references_operands():
    config = _f32()
    params = sm.init_params(config, jax.random.PRNGKey(11))
    run = ref.Pass(params, _tokens()[0, :-1], _dims(config))
    operands, want = run.routed_experts()
    got = sm.routed_experts(*operands, config=config)
    assert got.shape == want.shape == (1, 128, config.hidden_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # in the model's own dtype the rows are bfloat16: close, not equal
    low = sm.routed_experts(*operands, config=sm.SwaMoEConfig.tiny())
    err = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2


def test_train_step_carries_the_counts_of_every_expert_segment():
    """Through ShardedTrainStep: the step's metrics hold the LAST expert
    layer's routing counts and the rows of all four expert layers (two
    segments), its forced spans hold them as attributes, and the plans say
    which call ropes how."""
    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer
    from ray_tpu.util import tracing

    config = sm.SwaMoEConfig.tiny(fused_ce=True)
    mesh = build_mesh(axes={"fsdp": 1}, devices=jax.devices()[:1])
    ts = ShardedTrainStep(config, mesh, optimizer=default_optimizer(
        warmup_steps=1, total_steps=10, mu_dtype=jnp.bfloat16,
        nu_dtype=jnp.bfloat16))
    state = ts.init(jax.random.PRNGKey(0))
    before = jax.tree.map(np.asarray, state["params"])
    batch = {"tokens": jnp.asarray(_tokens())}
    losses = []
    for _ in range(4):
        state, metrics = ts.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    tokens = 2 * 128
    assert int(metrics["moe_rows_bound"]) == tokens * 3
    assert 0 < int(metrics["moe_rows_held"]) <= tokens * 3
    assert float(metrics["moe_load_mean"]) == int(metrics["moe_rows_held"]) / 4
    assert int(metrics["moe_load_max"]) >= float(metrics["moe_load_mean"])
    assert int(metrics["moe_rows_held_all_layers"]) > int(
        metrics["moe_rows_held"])       # four expert layers' against one's
    spans = [s for s in tracing.get_spans(("train.",))
             if s["name"] == "train.step"][-3:]
    assert [s["attributes"]["step"] for s in spans] == [1, 2, 4]
    for s in spans:
        assert {"moe_load_max", "moe_load_mean", "moe_rows_held",
                "moe_rows_held_all_layers", "moe_rows_bound"} <= set(
                    s["attributes"])
    after = jax.tree.map(np.asarray, state["params"])
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a != b).any()), before, after)))
    taken = dispatch.taken()
    plans = list(taken["flash_attention.plan"])
    assert any(",window32,visited100.0%,rope_in_kernel,operands_bshd," in p
               for p in plans), plans
    assert any("window" not in p and ",rope_in_kernel,operands_bshd," in p
               for p in plans), plans
    assert any(p.startswith("full_attention:in_kernel32of64_columns_reordered"
                            "_at_use_identity_tail,sliding_attention:"
                            "in_kernel64of64") for p in taken["swa_moe.rope"])


def test_layout_names_and_count():
    config = sm.SwaMoEConfig.tiny()
    params = jax.eval_shape(lambda: sm.init_params(config,
                                                   jax.random.PRNGKey(0)))
    assert sorted(params) == ["final_norm_w", "layers", "lm_head",
                              "tok_embed"]
    assert sorted(params["layers"]) == ["seg00", "seg01", "seg02"]
    seg = {k: v["0"] for k, v in params["layers"].items()}
    assert seg["seg00"]["w_gate"].shape == (1, 64, 128)
    assert seg["seg00"]["wq"].shape == (1, 64, 4 * 64)
    assert seg["seg01"]["wq"].shape == (3, 64, 6 * 64)      # by the heads
    assert seg["seg01"]["wo"].shape == (3, 6 * 64, 64)
    assert seg["seg01"]["wg"].shape == (3, 64, 6)
    assert seg["seg01"]["wk"].shape == (3, 64, 2 * 64)      # by the KV heads
    assert seg["seg02"]["wv"].shape == (1, 64, 2 * 64)
    assert seg["seg02"]["experts_gate"].shape == (1, 4, 64, 32)
    assert seg["seg02"]["router_w"].shape == (1, 64, 16)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == sm.num_params(config)
    axes = sm.logical_axes(config)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    # the cell's share of the published model, counted
    cell = sm.SwaMoEConfig(
        num_hidden_layers=5, num_experts=8, router_width=256,
        vocab_size=12544, num_attention_heads_per_layer=(48, 72, 72, 72, 48))
    assert sm.num_params(cell) == 811_017_216


@pytest.mark.parametrize("bad", [
    {"gating": "per-token"}, {"attention_bias": True},
    {"norm_topk_prob": False}, {"moe_router_logit_softcapping": 30.0},
    {"moe_apply_router_weight_on_input": True},
    {"tie_word_embeddings": True}, {"decoder_sparse_step": 2},
    {"first_held_expert": 14}, {"num_attention_heads_per_layer": (4, 5)},
    {"num_attention_heads_per_layer": (4, 5, 6, 6, 4)},
    {"layer_types": ("full_attention", "linear_attention") * 3},
    {"rope_parameters": {"full_attention": {"rope_type": "llama3",
                                            "rope_theta": 1e4},
                         "sliding_attention": {"rope_theta": 1e4}}}])
def test_what_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        sm.SwaMoEConfig.tiny(**bad)
