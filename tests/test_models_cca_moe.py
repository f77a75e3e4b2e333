"""models/cca_moe.py (compressed convolutional attention: unequal latents,
a shifted value half, a depthwise and a head-mixing causal convolution, the
q-k mean, an l2 norm with a temperature, a rotary half; an MLP router that
carries its state from layer to layer; top-1 experts under a raw gate; a
scaled residual path; a tied head) at tiny widths, kernels interpreted on
the CPU, against the benchmark's plain reference
(benchmark/reference/zaya1_cca_moe.py) on seeded weights."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import zaya1_cca_moe as ref
from ray_tpu.models import cca_moe as cm, common

SEQ = 64


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _f32(**kw):
    return cm.CcaMoEConfig.tiny(dtype=jnp.float32, remat=False, **kw)


def _dims(config):
    """From the config as a configuration file's `model` group holds it."""
    model = {f.name: getattr(config, f.name)
             for f in dataclasses.fields(config)}
    return ref.dims_from_config({**model,
                                 "rope_parameters": cm.PUBLISHED_ROPE})


@functools.cache
def _init(config, seed):
    """`init_params` under one jit a config: eagerly it is hundreds of
    small programs."""
    return jax.jit(lambda key: cm.init_params(config, key))(
        jax.random.PRNGKey(seed))


def _tokens(rows=2, seq=SEQ, vocab=256, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (rows, seq + 1), 0, vocab))


def test_the_tiny_size_has_what_the_cell_has():
    """Four layers of the one kind in one scanned segment, group 2, latents
    of unequal width under a narrower hidden size, a rotary half, 4 of 16
    experts held, one a token; the cell's and the published size's counts."""
    config = cm.CcaMoEConfig.tiny()
    assert cm.segments(config) == [(cm.HYBRID, 0, 4)]
    assert config.latents == (256, 128) and config.hidden_size == 96
    assert config.rotary_width * 2 == config.head_dim
    assert config.experts_held == (0, 4) and config.router_width == 16
    assert config.num_experts_per_tok == 1
    params = _init(config, 0)
    assert "lm_head" not in params      # tied
    assert sum(a.size for a in jax.tree.leaves(params)) \
        == cm.num_params(config)
    # the cell's cut: ISSUE 44's count, a layer's parts by hand
    cell = cm.CcaMoEConfig(num_hidden_layers=4, vocab_size=32784)
    layer = (16 * 3 * 2048 * 2048 + 5_242_880 + 3_840 + 328_960 + 2
             + 660_752 + 4_096 + 16_384)
    assert layer == 207_583_506
    assert cm.num_params(cell) == 4 * layer + 32_784 * 2048 + 2048 \
        == 897_477_704
    assert cell.latents == (1024, 256) and cell.rope_theta == 5e6
    assert round(cm.num_params(cm.CcaMoEConfig()) / 1e9, 2) == 8.84
    # drawn, not constants
    lp = params["layers"]["seg00"]["0"]
    for name in ("attn_sr", "ffn_sh", "ln1_w", "tau", "router_carry"):
        assert abs(float(jnp.mean(lp[name])) - 1.0) < 0.15, name
        assert float(jnp.std(lp[name])) > 0.02, name
    for name in ("conv0_b", "conv1_b", "router_b1"):
        assert 0.05 < float(jnp.std(lp[name])) < 0.2, name
    for name in ("attn_br", "ffn_bh"):      # under the embedding's 1 / sqrt(E)
        assert 0.005 < float(jnp.std(lp[name])) < 0.02, name
    frozen = cm.not_trained(config)
    assert [k for k, v in frozen["layers"]["seg00"]["0"].items() if v] \
        == ["router_bias"]


@pytest.mark.parametrize("fused_ce", [False, True])
def test_token_nll_matches_the_reference(fused_ce):
    config = _f32(fused_ce=fused_ce)
    params = _init(config, 3)
    tokens = _tokens()
    got = jax.jit(functools.partial(cm.token_nll, config=config))(
        params, {"tokens": jnp.asarray(tokens)})
    want = ref.batch_token_nll(params, tokens, _dims(config))
    # the fused cross-entropy multiplies in bfloat16 whatever the model's
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2 if fused_ce else 3e-4)
    assert abs(float(got.mean()) - np.log(256)) < 1.0


def test_gradient_matches_the_reference_layer_by_layer():
    """jax.grad of the program's loss (the convolutions', the mean's, the l2
    norm's and the column reordering's backward, the head-64 flash VJP under
    a half rope, the router's MLP and its carry, the raw gate's gradient
    through the gathers' VJPs, the grouped kernels', the residual scales',
    the tied embedding's two uses) against the reference's gradient walked
    back a layer at a time with the router state's cotangent."""
    config = _f32()
    params = _init(config, 4)
    tokens = _tokens(rows=1)
    got = jax.jit(jax.grad(lambda p: cm.loss_fn(
        p, {"tokens": jnp.asarray(tokens)}, config)))(params)
    run = ref.Pass(params, tokens[0, :-1], _dims(config), for_grads=True)
    seen, zero = 0, []
    for path, grad in run.grads(tokens[0, 1:]):
        for name, w in (grad.items() if isinstance(grad, dict)
                        else [(None, grad)]):
            g = got[path[0]] if name is None \
                else got["layers"][path[1]][path[2]][name][path[3]]
            w = np.asarray(w)
            if not w.any():
                zero.append((path[-1], name))
                assert not np.asarray(g).any()
                continue
            assert np.linalg.norm(np.asarray(g) - w) \
                <= 2e-3 * np.linalg.norm(w), (path, name)
            seen += 1
    # the bias gets no gradient anywhere, nor layer 0's alpha
    assert sorted(zero) == sorted(
        [(rep, "router_bias") for rep in range(4)] + [(0, "router_carry")])
    assert seen == 2 + 4 * len(cm._layer_shapes(config)) - 5


def test_the_probe_runs_the_mix_alone_on_the_references_operands():
    config = _f32()
    params = _init(config, 11)
    run = ref.Pass(params, _tokens()[0, :-1], _dims(config))
    operands, want = run.cca_mix()
    assert len(operands) == 10 and operands[0].shape == (1, SEQ, 96)
    assert want.shape == (1, SEQ, 256 + 128 + 128)
    got = cm.cca_mix(*operands, config=config)
    assert got.dtype == jnp.float32
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 2e-6
    # Q and K are l2-normalised to sqrt(d), K times tau
    q = np.asarray(got[0, :, :256]).reshape(SEQ, 4, 64)
    np.testing.assert_allclose(np.linalg.norm(q, axis=-1), 8.0, rtol=1e-4)
    # with the matrices as the layer hands them over (bfloat16): close
    low = cm.cca_mix(*(a.astype(jnp.bfloat16) if a.ndim >= 2 and i not in
                       (5, 6, 8) else a for i, a in enumerate(operands)),
                     config=config)
    err = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2


def _plain_residual(x, f, lp, prefix):
    return x + f


def _zero_centred(x, w, eps):
    return _rms_norm(x, 1.0 + w, eps)


def _values_unshifted(u, wv1, wv2):
    return jnp.concatenate([cm._matmul(u, wv1), cm._matmul(u, wv2)], axis=-1)


def _depthwise_only(x, heads, w0, b0, w1, b1):
    return common.causal_depthwise_conv(x, w0.astype(jnp.float32),
                                        b0.astype(jnp.float32))


def _last_tap_only(x, heads, w0, b0, w1, b1):
    return _two_convs(x, heads, w0[-1:], b0, w1, b1)


def _no_mean(q0, k0, heads, kv):
    return jnp.zeros_like(q0), jnp.zeros_like(k0)


def _mean_over_the_wrong_heads(q0, k0, heads, kv):
    mean_q, _ = _qk_means(q0, k0, heads, kv)
    b, s, _ = mean_q.shape      # heads j, j + kv, .. instead of a group's
    mean_k = mean_q.reshape(b, s, heads // kv, kv, -1).mean(2)
    return mean_q, mean_k.reshape(b, s, -1)


def _no_tau(x, heads, d, tau=None):
    return _l2_normalised(x, heads, d)


def _no_l2(x, heads, d, tau=None):
    return x


def _no_carry(h, r_prev, lp, c):
    return _route(h, jnp.zeros_like(r_prev), lp, c)


def _renormalised_gate(scores, bias, **kw):
    return _select(scores, bias, **{**kw, "gate_rule": "renormalised"})


_two_convs, _qk_means, _l2_normalised = (
    cm._two_convs, cm._qk_means, cm._l2_normalised)
_route, _select, _rms_norm = cm.route, cm.moe.select_experts, cm.rms_norm
# (what is replaced, by what): each of the configuration's assumptions
# A1-A7 read another way, one at a time
BREAKS = {
    "A1_residual_scales_left_out": (cm, "_residual", _plain_residual),
    "A2_a_plain_norm_read_as_zero_centred": (cm, "rms_norm", _zero_centred),
    "A3_the_second_value_half_from_the_current_token": (
        cm, "_values", _values_unshifted),
    "A4_a_tap_of_the_depthwise_conv_dropped": (
        cm, "_two_convs", _last_tap_only),
    "A4_the_head_mixing_conv_left_out": (cm, "_two_convs", _depthwise_only),
    "A5_the_qk_mean_left_out": (cm, "_qk_means", _no_mean),
    "A5_the_keys_mean_over_the_wrong_heads": (
        cm, "_qk_means", _mean_over_the_wrong_heads),
    "A6_tau_left_out": (cm, "_l2_normalised", _no_tau),
    "A6_the_l2_norm_left_out": (cm, "_l2_normalised", _no_l2),
    "A7_the_carry_left_out": (cm, "route", _no_carry),
    "A7_the_gate_renormalised_to_one": (
        cm.moe, "select_experts", _renormalised_gate),
}


@pytest.fixture(scope="module")
def two_layers():
    """(config, params, tokens, the reference's per-token NLL): two layers,
    so that the carry crosses one edge, all sixteen experts held, so that
    every token's expert is here; one row of 64 tokens."""
    config = _f32(num_hidden_layers=2, num_experts=16)
    params = _init(config, 3)
    tokens = _tokens(rows=1, seq=64)
    return config, params, tokens, ref.batch_token_nll(params, tokens,
                                                       _dims(config))


def _nll_rms(two_layers):
    config, params, tokens, want = two_layers
    got = jax.jit(lambda p, b: cm.token_nll(p, b, config))(
        params, {"tokens": jnp.asarray(tokens)})
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)))


@pytest.mark.parametrize("name", sorted(BREAKS))
def test_each_assumption_read_another_way_moves_the_nll(monkeypatch, name,
                                                        two_layers):
    """The sound program reads under 1e-4 (root mean square over the
    positions of the difference in per-token NLL, float32); every break
    reads at least a hundred times that."""
    where, attr, broken = BREAKS[name]
    monkeypatch.setattr(where, attr, broken)
    assert _nll_rms(two_layers) > 1e-2, name


def test_the_sound_program_reads_under_the_breaks_limit(two_layers):
    assert _nll_rms(two_layers) < 1e-4


def test_a_sequences_first_token_sees_zero_history():
    """Both convolutions and the shifted value half start from nothing: the
    mix of position 0 is what a one-token sequence gives, whatever follows;
    and position t reads nothing after t."""
    config = _f32()
    lp = jax.tree.map(lambda a: a[0],
                      _init(config, 5)["layers"]["seg00"]["0"])
    weights = [lp[n] for n in ref.MIX_OPERANDS]
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 96))
    mix = jax.jit(functools.partial(cm.cca_mix, config=config))
    whole = mix(u, *weights)
    alone = mix(u[:, :1], *weights)
    np.testing.assert_allclose(np.asarray(whole[:, :1]), np.asarray(alone),
                               atol=1e-5)
    # the first token's shifted value half is exactly zero
    assert not np.asarray(whole[0, 0, -64:]).any()
    assert np.asarray(whole[0, 1, -64:]).any()
    # causal: what follows position 7 does not reach it
    other = mix(u.at[:, 8:].set(0.0), *weights)
    np.testing.assert_allclose(np.asarray(whole[:, :8]),
                               np.asarray(other[:, :8]), atol=1e-5)
    # the taps reach back exactly 1 + 1 positions
    moved = mix(u.at[:, 3].add(1.0), *weights)
    changed = np.asarray(jnp.abs(moved - whole).max(axis=(0, 2)) > 1e-6)
    assert list(np.nonzero(changed)[0]) == [3, 4, 5]


def test_the_router_state_crosses_layers_and_carries_the_gradient():
    """Layer 0 takes no state (alpha_0 does nothing); a later layer's
    routing moves with the earlier layer's state; the loss's gradient
    reaches alpha and, through the carry alone, the FIRST layer's W_rd from
    the LAST layer's gate."""
    config = _f32()
    params = _init(config, 7)
    lp = jax.tree.map(lambda a: a[1], params["layers"]["seg00"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(8), (SEQ, 96))
    r_prev = jax.random.normal(jax.random.PRNGKey(9), (SEQ, 32))
    route = jax.jit(functools.partial(cm.route, c=config))
    idx0, gates0, r0 = route(h, jnp.zeros_like(r_prev), lp)
    idx1, gates1, r1 = route(h, r_prev, lp)
    np.testing.assert_allclose(
        np.asarray(r1 - r0), np.asarray(lp["router_carry"] * r_prev),
        atol=1e-5)
    assert (np.asarray(idx0) != np.asarray(idx1)).mean() > 0.2
    assert gates1.shape == (SEQ, 1) and float(gates1.max()) < 1.0
    # the state a layer hands on is its own r AFTER the carry's step
    want = ref._router(h, r_prev, jax.tree.map(
        lambda a: a.astype(jnp.float32), lp), _dims(config), False)
    np.testing.assert_array_equal(np.asarray(idx1), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(r1), np.asarray(want[2]),
                               atol=1e-5)

    def last_gates(params):
        """The sum of the last layer's gates, the stream held fixed: only
        the router's state joins the layers."""
        r, total = jnp.zeros_like(r_prev), 0.0
        for layer in range(4):
            lp = jax.tree.map(lambda a: a[layer],
                              params["layers"]["seg00"]["0"])
            _, gates, r = cm.route(h, r, lp, config)
            total = jnp.sum(gates)
        return total

    g = jax.jit(jax.grad(last_gates))(params)["layers"]["seg00"]["0"]
    assert float(jnp.abs(g["router_down_w"][0]).max()) > 0
    assert not np.asarray(g["router_carry"][0]).any()
    assert all(float(jnp.abs(g["router_carry"][i]).max()) > 0
               for i in (1, 2, 3))
    assert not np.asarray(g["router_bias"]).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips, experts 0-7 and 8-15: the routed sums the program's layer
    gives for the two shares (no part is computed by both) are what the
    uncut sixteen-expert reference layer gives."""
    config = _f32(num_experts=16, router_width=16)
    lp = jax.tree.map(
        lambda a: a[1], _init(config, 5)
        ["layers"]["seg00"]["0"])
    h = jax.random.normal(jax.random.PRNGKey(6), (SEQ, 96))
    r_prev = jax.random.normal(jax.random.PRNGKey(7), (SEQ, 32))
    total, rows = 0.0, 0
    for first in (0, 8):
        held = slice(first, first + 8)
        share = dataclasses.replace(config, num_experts=8,
                                    first_held_expert=first)
        mine = {k: v[held] if k.startswith("experts_") else v
                for k, v in lp.items()}
        part, r, stats = jax.jit(functools.partial(
            cm._routed_part, c=share))(h, r_prev, mine)
        total, rows = total + part, rows + int(stats["rows_held"])
        alone = ref.whole_layer_ffn(h, r_prev, mine, _dims(config),
                                    (first, 8))
        np.testing.assert_allclose(np.asarray(part), np.asarray(alone),
                                   atol=2e-5, rtol=1e-5)
        assert 0 < int(stats["rows_held"]) < SEQ
    assert rows == SEQ      # every token's one expert, once
    want = ref.whole_layer_ffn(h, r_prev, lp, _dims(config), (0, 16))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=3e-5, rtol=1e-5)
    # all sixteen held: every row is here whatever the routing
    _, _, stats = jax.jit(functools.partial(cm._routed_part, c=config))(
        h, r_prev, lp)
    assert int(stats["rows_held"]) == int(stats["rows_bound"]) == SEQ


def test_the_half_rope_reaches_the_kernels_by_columns_and_a_tail():
    """`_rotary_first_halves` on a head's published columns and
    `kernel_tables`: the kernels' whole-head turn (pair i with i + d/2) of
    the reordered head is the reference's turn of the first half,
    reordered."""
    from ray_tpu.ops.attention import rope_reference

    config = _f32()
    d, r = config.head_dim, config.rotary_width
    assert (d, r) == (64, 32)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 3 * d))
    cos, sin = cm.kernel_tables(40, config)
    assert cos.shape == (40, d // 2)
    assert bool((cos[:, r // 2:] == 1).all() & (sin[:, r // 2:] == 0).all())
    got = rope_reference(
        cm._rotary_first_halves(x, 3, config).reshape(1, 40, 3, d),
        cos[None], sin[None])
    want = cm._rotary_first_halves(
        ref._rope(x[0].reshape(40, 3, d), r, config.rope_theta
                  ).reshape(1, 40, 3 * d), 3, config)
    np.testing.assert_allclose(np.asarray(got).reshape(1, 40, 3 * d),
                               np.asarray(want), atol=1e-5)
    order = np.asarray(cm._rotary_first_halves(jnp.arange(d), 1, config))
    assert sorted(order) == list(range(d))
    assert list(order[:r // 2]) == list(range(r // 2))
    assert list(order[d // 2:d // 2 + r // 2]) == list(range(r // 2, r))


def test_train_step_carries_the_counts_and_the_plans():
    """Through ShardedTrainStep: the loss falls, the step's metrics hold the
    LAST layer's routing counts and the rows of all four layers, its forced
    spans hold them as attributes, the selection bias stays as drawn, and
    the plans say what ran."""
    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer
    from ray_tpu.util import tracing

    config = cm.CcaMoEConfig.tiny(fused_ce=True, num_experts=16)
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
    tokens = 2 * SEQ
    # all sixteen held, one expert a token: the rows are the tokens
    assert int(metrics["moe_rows_bound"]) == tokens
    assert int(metrics["moe_rows_held"]) == tokens
    assert int(metrics["moe_rows_held_all_layers"]) == 4 * tokens
    assert float(metrics["moe_load_mean"]) == tokens / 16
    spans = [s for s in tracing.get_spans(("train.",))
             if s["name"] == "train.step"][-2:]    # steps 1, 2, 4, ..
    assert [s["attributes"]["step"] for s in spans] == [1, 2]
    assert {"moe_load_max", "moe_load_mean", "moe_rows_held",
            "moe_rows_held_all_layers", "moe_rows_bound",
            "remat"} <= set(spans[0]["attributes"])
    after = jax.tree.map(np.asarray, state["params"])
    moved = jax.tree.map(lambda a, b: bool((a != b).any()), before, after)
    lp = moved["layers"]["seg00"]["0"]
    assert not lp.pop("router_bias")
    assert all(jax.tree.leaves(moved))
    taken = dispatch.taken()
    assert list(taken["cca_moe.mix"]) == [
        "taps2+2,heads4over2,latent256+128,vshift,l2tau,xla"]
    assert list(taken["cca_moe.rope"]) == [
        "hybrid:in_kernel32of64_columns_reordered_at_use_identity_tail"]
    assert any(",rope_in_kernel,operands_bshd,heads2x64" in p
               for p in taken["flash_attention.plan"])
    assert any(p.endswith(",groups16") for p in taken["grouped_matmul.plan"])
    assert any(p.startswith("kept:") for p in taken["train.remat"])


def test_layout_names_and_scopes():
    """`layers/seg00/0/<leaf>` with a leading axis of repeats (what the
    benchmark's driver reads), the logical axes beside every leaf, and the
    mix under `attn.mix` INSIDE `attn.full`, the router under `moe.route`."""
    config = cm.CcaMoEConfig.tiny()
    shapes = jax.eval_shape(lambda: cm.init_params(config,
                                                   jax.random.PRNGKey(0)))
    axes = cm.logical_axes(config)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    lp = shapes["layers"]["seg00"]["0"]
    assert lp["wq"].shape == (4, 96, 256) and lp["wk"].shape == (4, 96, 128)
    assert lp["wv1"].shape == lp["wv2"].shape == (4, 96, 64)
    assert lp["conv0_w"].shape == (4, 2, 384)
    assert lp["conv1_w"].shape == (4, 6, 2, 64, 64)
    assert lp["wo"].shape == (4, 256, 96) and lp["tau"].shape == (4, 2)
    assert lp["router_w3"].shape == (4, 32, 16)
    tokens = jnp.asarray(_tokens(rows=1))
    text = jax.jit(lambda p: cm.loss_fn(p, {"tokens": tokens}, config)
                   ).lower(shapes).as_text(debug_info=True)
    for scope in (common.ATTN_FULL, common.MLP, common.MOE_ROUTE,
                  common.MOE_DISPATCH, common.MOE_EXPERTS,
                  common.MOE_COMBINE, common.LOSS, common.EMBED):
        assert f"/{scope}/" in text, scope
    assert f"/{common.ATTN_FULL}/{common.ATTN_MIX}/" in text
    assert common.ATTN_MIX not in common.SCOPES     # it decides no part
    assert common.ATTN_SLIDING not in text and common.SSM not in text


@pytest.mark.parametrize("bad", [
    {"layer_types": ("hybrid", "hybrid_sliding", "hybrid", "hybrid")},
    {"sliding_window": 4096}, {"attention_bias": True},
    {"tie_word_embeddings": False}, {"hidden_act": "gelu"},
    {"partial_rotary_factor": 0.25}, {"num_key_value_heads": 1},
    {"first_held_expert": 14}, {"cca_time1": 0}])
def test_what_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        cm.CcaMoEConfig.tiny(**bad)
