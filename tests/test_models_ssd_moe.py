"""models/ssd_moe.py (Mamba-2 mixers with a grouped gated norm, un-roped GQA
attention, sigmoid-routed UNGATED relu^2 experts beside a shared one, ONE
sublayer a layer) at tiny widths, kernels interpreted on the CPU, against the
benchmark's plain reference (benchmark/reference/nemotron_h_ssd_moe.py) on
seeded weights; each assumed form broken in turn in the reference."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h_ssd_moe as ref
from ray_tpu.models import common, ssd_moe as sm


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
    return sm.SsdMoEConfig.tiny(dtype=jnp.float32, remat=False, **kw)


def _dims(config):
    """From the config as a configuration file's `model` group holds it."""
    return ref.dims_from_config({f.name: getattr(config, f.name)
                                 for f in dataclasses.fields(config)})


def _tokens(rows=2, seq=96, vocab=256, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (rows, seq + 1), 0, vocab))


def test_the_tiny_size_has_what_the_cell_has():
    """Every kind of layer, heads over groups, GQA, held < routed, an expert
    width half a lane tile over a whole one, `expand` x hidden that is NOT
    the heads' width (B3); the cell's count to the unit; the published
    pattern's 23 / 23 / 6."""
    config = sm.SsdMoEConfig.tiny()
    assert config.layer_kinds == ("M", "E", "M", "*", "E")
    assert sm.segments(config) == [("M", 0, 1), ("E", 1, 1), ("M", 2, 1),
                                   ("*", 3, 1), ("E", 4, 1)]
    assert config.mamba_num_heads // config.n_groups == 2
    assert config.d_inner == 256 != config.expand * config.hidden_size
    assert config.moe_intermediate_size % 128 == 64
    assert config.experts_held == (0, 4) and config.router_width == 16
    params = sm.init_params(config, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) \
        == sm.num_params(config)
    mamba = params["layers"]["seg00"]["0"]
    assert mamba["w_in"].shape == (1, 64, 256 + 768 + 4)
    assert mamba["conv_w"].shape == (1, 4, 768) \
        and mamba["conv_b"].shape == (1, 768)
    experts = params["layers"]["seg01"]["0"]
    assert "experts_gate" not in experts and "shared_gate" not in experts
    assert experts["experts_up"].shape == (1, 4, 64, 192)
    cell = sm.SsdMoEConfig(num_hidden_layers=9,
                           hybrid_override_pattern="MEMEM*EME",
                           n_routed_experts=8, router_width=128,
                           vocab_size=16384)
    assert sm.num_params(cell) == 666_963_456
    whole = sm.SsdMoEConfig()
    assert [whole.layer_kinds.count(k) for k in "ME*"] == [23, 23, 6]
    assert round(sm.num_params(whole) / 1e9, 1) == 31.6
    # drawn, not constants
    assert abs(float(jnp.mean(mamba["gn_w"])) - 1.0) < 0.1 < 10 * float(
        jnp.std(mamba["gn_w"]))
    assert float(jnp.std(mamba["conv_b"])) > 0.05
    assert float(jnp.min(mamba["A_log"])) >= 0 and float(
        jnp.max(jax.nn.softplus(mamba["dt_bias"]))) <= 0.1001
    assert bool(jnp.all(mamba["D"] == 1.0))
    # the matrices back to the stream: every output column centred over its
    # fan-in, so that a positive-mean hidden adds no one vector to every token
    full = params["layers"]["seg03"]["0"]
    for w in (mamba["w_out"], full["wo"], experts["experts_down"],
              experts["shared_down"]):
        assert float(jnp.max(jnp.abs(jnp.mean(w, axis=-2)))) < 1e-6
        assert abs(float(jnp.std(w)) * math.sqrt(w.shape[-2]) - 1.0) < 0.1
    assert float(jnp.max(jnp.abs(jnp.mean(mamba["w_in"], axis=-2)))) > 1e-3
    frozen = sm.not_trained(config)
    assert frozen["layers"]["seg01"]["0"]["router_bias"] is True
    assert sum(jax.tree.leaves(frozen)) == 2


def test_the_published_pattern_lays_out_and_traces():
    """All 52 characters at tiny widths: 23 mamba, 23 expert and 6 attention
    layers in the published order, each its own leaves, and the loss traces
    through them."""
    config = sm.SsdMoEConfig.tiny(
        num_hidden_layers=52,
        hybrid_override_pattern=sm.SsdMoEConfig().hybrid_override_pattern)
    shapes = jax.eval_shape(
        lambda: sm.init_params(config, jax.random.PRNGKey(0)))
    kinds = {"w_in": "M", "wq": "*", "router_w": "E"}
    laid = [next(kinds[n] for n in kinds if n in seg["0"])
            for _, seg in sorted(shapes["layers"].items())
            for _ in range(seg["0"]["ln_w"].shape[0])]
    assert "".join(laid) == config.hybrid_override_pattern
    out = jax.eval_shape(
        lambda p: sm.loss_and_metrics(
            p, {"tokens": jnp.zeros((1, 33), jnp.int32)}, config), shapes)
    assert out[0].shape == () and "moe_rows_held_all_layers" in out[1]


@pytest.mark.parametrize("fused_ce", [False, True])
def test_token_nll_matches_the_reference(fused_ce):
    config = _f32(fused_ce=fused_ce)
    params = sm.init_params(config, jax.random.PRNGKey(3))
    tokens = _tokens()
    got = _nll(params, {"tokens": jnp.asarray(tokens)}, config)
    want = ref.batch_token_nll(params, tokens, _dims(config))
    # the fused cross-entropy multiplies in bfloat16 whatever the model's
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2 if fused_ce else 3e-4)
    assert abs(float(got.mean()) - np.log(256)) < 1.0
    # a row's first token sees zero history: a row alone reads the same
    alone = _nll(params, {"tokens": jnp.asarray(tokens[1:])}, config)
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(got[1]),
                               atol=3e-2 if fused_ce else 1e-5)


def test_gradient_matches_the_reference_layer_by_layer():
    """jax.grad of the program's loss (the recurrence's backward kernel with
    the group's sums inside it, the running sum's transpose, the conv and
    its bias, the gate and the grouped norm, the un-roped flash VJP, the
    grouped kernels at an off-grid width and the gathers' VJPs) against the
    reference's gradient walked back a layer at a time; the selection bias
    gets none."""
    config = _f32()
    params = sm.init_params(config, jax.random.PRNGKey(4))
    tokens = _tokens(rows=1)
    got = jax.jit(jax.grad(lambda p: sm.loss_fn(
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
            if name == "router_bias":
                assert not np.asarray(g).any() and not w.any()
            seen += 1
    # three at the top; a mamba layer's 9 leaves, attention's 5, experts' 7
    assert seen == 3 + 2 * 9 + 5 + 2 * 7


# -- each assumed form broken in turn, in the reference ----------------------

def _gate_behind_the_norm(u, lp, d):
    T = u.shape[0]
    operands, z = ref._scan_operands(u, lp, d)
    y = ref._scan_output(*operands, d)
    y = ref._norm(y.reshape(T, d["G"], -1), lp["gn_w"].reshape(d["G"], -1),
                  d["eps"]).reshape(T, -1) * jax.nn.silu(z)
    return y @ lp["w_out"]


def _one_norm_group(u, lp, d):
    T = u.shape[0]
    operands, z = ref._scan_operands(u, lp, d)
    y = ref._scan_output(*operands, d).reshape(T, -1) * jax.nn.silu(z)
    return ref._norm(y, lp["gn_w"], d["eps"]) @ lp["w_out"]


def _operands_with(change):
    whole = ref._scan_operands

    def broken(u, lp, d):
        (x, dt, a, B, C, D), z = whole(u, lp, d)
        return change(x, dt, a, B, C, D), z
    return broken


def _roped_attention(u, lp, d):
    T, hd = u.shape[0], d["d"]
    angle = jnp.arange(T)[:, None] * 10000.0 ** (
        -2.0 * jnp.arange(hd // 2) / hd)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]

    def rope(x):
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    a = ref._grouped_attention(
        rope((u @ lp["wq"]).reshape(T, d["heads"], hd)),
        rope((u @ lp["wk"]).reshape(T, d["kv"], hd)),
        (u @ lp["wv"]).reshape(T, d["kv"], hd))
    return a.reshape(T, -1) @ lp["wo"]


def _select_without(renormalised):
    def select(h, router_w, router_bias, d):
        scores = jax.nn.sigmoid(h @ router_w)
        _, sel = jax.lax.top_k(scores + router_bias, d["top_k"])
        picked = jnp.take_along_axis(scores, sel, axis=-1)
        if renormalised:        # .. but the bias in the gates too
            picked = picked + router_bias[sel]
            return sel, picked / jnp.sum(picked, -1, keepdims=True) \
                * d["scale"]
        return sel, picked * d["scale"]
    return select


BREAKS = {
    "gate_behind_the_norm": {"_mamba_mixer": _gate_behind_the_norm},
    "one_norm_group_in_place_of_two": {"_mamba_mixer": _one_norm_group},
    "conv_bias_dropped": {"_causal_conv": lambda x, w, b: (
        _CONV(x, w, jnp.zeros_like(b)))},
    "conv_tap_dropped": {"_causal_conv": lambda x, w, b: (
        _CONV(x, w.at[0].set(0.0), b))},
    "b_and_c_swapped": {"_scan_operands": _operands_with(
        lambda x, dt, a, B, C, D: (x, dt, a, C, B, D))},
    "d_left_out": {"_scan_operands": _operands_with(
        lambda x, dt, a, B, C, D: (x, dt, a, B, C, 0.0 * D))},
    "b_of_the_wrong_group": {"_scan_operands": _operands_with(
        lambda x, dt, a, B, C, D: (x, dt, a, B[:, ::-1], C, D))},
    "a_rope_added": {"_full_attention": _roped_attention},
    "silu_gate_in_place_of_relu2": {"_relu2_mlp": lambda h, w_up, w_down: (
        jax.nn.silu(h @ w_up) * (h @ w_up)) @ w_down},
    "relu_in_place_of_relu2": {"_relu2_mlp": lambda h, w_up, w_down: (
        jax.nn.relu(h @ w_up) @ w_down)},
    "scale_left_out": {"scale": 1.0},
    "gates_not_renormalised": {"_select": _select_without(False)},
    "bias_in_the_gates": {"_select": _select_without(True)},
}
_CONV = ref._causal_conv


def _reference_nll(params, tokens, dims):
    """The reference's layers walked OUTSIDE its jitted programs, so that a
    patched function is the one that runs."""
    with jax.default_matmul_precision("highest"):
        run = ref.Pass.__new__(ref.Pass)
        x = params["tok_embed"][tokens[:-1]].astype(jnp.float32)
        for kind, where in zip(dims["kinds"],
                               ref._layers_in_order(params)):
            lp = ref._layer_params(params, where)
            x = x + ref._SUBLAYER[kind](ref._norm(x, lp["ln_w"], dims["eps"]),
                                        lp, dims)
        run.params, run.final = params, ref._norm(
            x, params["final_norm_w"], dims["eps"])
        return run.token_nll(tokens[1:])


@pytest.fixture(scope="module")
def sound():
    config = _f32()
    params = sm.init_params(config, jax.random.PRNGKey(6))
    tokens = _tokens(rows=1, seq=64)
    # for this call alone: left in os.environ it outlives the module in its
    # xdist worker and turns the next file's dispatch to the interpreter
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        got = sm.token_nll(params, {"tokens": jnp.asarray(tokens)},
                           config)[0]
    return config, params, tokens[0], np.asarray(got)


def test_the_sound_reference_is_within_the_limit(sound):
    """The program through the INTERPRETED passes of ops/mixer_chain.py (the
    conv with its bias and the cut; the gate and the norm by groups), which
    the breaks below are held against."""
    from ray_tpu.ops import dispatch, mixer_chain

    config, params, tokens, got = sound
    assert "interpret" in dispatch.taken()["ssd_chain"]
    inner, wide = config.d_inner, config.conv_channels
    assert mixer_chain.split_path(
        jnp.zeros((1, 64, wide)), jnp.zeros((config.conv_kernel, wide)),
        (inner, (wide - inner) // 2, (wide - inner) // 2)) == "interpret"
    assert mixer_chain.norm_path(jnp.zeros((1, 64, inner)),
                                 config.n_groups) == "interpret"
    want = _reference_nll(params, tokens, _dims(config))
    assert np.abs(got - np.asarray(want)).max() < 3e-4


@pytest.mark.parametrize("name", sorted(BREAKS))
def test_a_broken_form_moves_the_nll_past_the_limit(name, sound, monkeypatch):
    """B1-B8: the reference with ONE form read otherwise differs from the
    program by over thirty times what the sound one does."""
    config, params, tokens, got = sound
    dims = _dims(config)
    for attr, value in BREAKS[name].items():
        if attr in dims:
            dims[attr] = value
        else:
            monkeypatch.setattr(ref, attr, value)
            if attr in ("_mamba_mixer", "_full_attention"):
                kind = "M" if attr == "_mamba_mixer" else "*"
                monkeypatch.setitem(ref._SUBLAYER, kind, value)
    if "_relu2_mlp" in BREAKS[name] or "_select" in BREAKS[name]:
        monkeypatch.setitem(ref._SUBLAYER, "E", lambda u, lp, d: (
            ref._held_experts_sum(u, lp["router_w"], lp["router_bias"],
                                  lp["experts_up"], lp["experts_down"], d)
            + ref._relu2_mlp(u, lp["shared_up"], lp["shared_down"])))
    want = np.asarray(_reference_nll(params, tokens, dims))
    assert np.abs(got - want).max() > 1e-2, name


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips, four of sixteen experts each (every `first_held_expert`):
    the routed parts that the program's layer gives for the four shares,
    summed, with the shared expert counted ONCE, are the uncut layer's
    feed-forward, the program's with all sixteen held and the reference's."""
    whole = _f32(n_routed_experts=16, router_width=16)
    lp = jax.tree.map(lambda a: a[0], sm.init_params(
        whole, jax.random.PRNGKey(8))["layers"]["seg01"]["0"])
    u = jax.random.normal(jax.random.PRNGKey(9), (2, 48, 64))
    flat = u.reshape(-1, 64)
    shared = common.relu2_mlp(u, lp["shared_up"], lp["shared_down"],
                              jnp.float32)

    def routed(config, first):
        last = first + config.n_routed_experts
        return sm._routed_part(flat, lp["router_w"], lp["router_bias"],
                               lp["experts_up"][first:last],
                               lp["experts_down"][first:last], config)

    uncut, stats = routed(whole, 0)
    parts = [routed(_f32(first_held_expert=first), first)
             for first in (0, 4, 8, 12)]
    summed = sum(y for y, _ in parts)
    np.testing.assert_allclose(summed, uncut, atol=2e-5)
    assert sum(int(s["rows_held"]) for _, s in parts) \
        == int(stats["rows_held"]) == flat.shape[0] * 3
    want = ref.whole_layer_ffn(flat, lp, _dims(whole), (0, 16))
    np.testing.assert_allclose(summed + shared.reshape(-1, 64), want,
                               atol=5e-5)
    one = ref.whole_layer_ffn(
        flat, {**lp, "experts_up": lp["experts_up"][4:8],
               "experts_down": lp["experts_down"][4:8]}, _dims(whole), (4, 4),
        with_shared=False)
    np.testing.assert_allclose(parts[1][0], one, atol=5e-5)


def test_the_probe_runs_the_scan_alone_on_the_references_operands():
    config = _f32()
    params = sm.init_params(config, jax.random.PRNGKey(11))
    run = ref.Pass(params, _tokens()[0, :-1], _dims(config))
    operands, want = run.ssd_scan()
    assert operands[0].shape == (1, 96, 4, 64) and operands[3].shape == (
        1, 96, 2, 128) and want.shape == (1, 96, 4, 64)
    got = sm.ssd_scan(*operands, config=config)
    assert got.dtype == jnp.float32
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 3e-5
    # with the operands as the mixer hands them over (bfloat16): close
    low = sm.ssd_scan(*(a.astype(jnp.bfloat16) if a.ndim == 4 else a
                        for a in operands), config=config)
    err = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2


def test_train_step_carries_the_counts_and_the_plans(monkeypatch):
    """Through ShardedTrainStep: the loss falls, the step's metrics hold the
    LAST expert layer's routing counts and the rows of both expert layers,
    its forced spans hold them as attributes, the selection bias stays as
    drawn, and the plans say what ran."""
    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer
    from ray_tpu.util import tracing

    monkeypatch.setattr(dispatch, "_taken", {})
    config = sm.SsdMoEConfig.tiny(fused_ce=True)
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
        metrics["moe_rows_held"])       # two expert layers' against one's
    spans = [s for s in tracing.get_spans(("train.",))
             if s["name"] == "train.step"][-2:]    # steps 1, 2, 4, ..
    assert [s["attributes"]["step"] for s in spans] == [1, 2]
    assert {"moe_load_max", "moe_load_mean", "moe_rows_held",
            "moe_rows_held_all_layers", "moe_rows_bound",
            "remat"} <= set(spans[0]["attributes"])
    after = jax.tree.map(np.asarray, state["params"])
    moved = jax.tree.map(lambda a, b: bool((a != b).any()), before, after)
    frozen = sm.not_trained(config)
    assert all(m != f for m, f in zip(jax.tree.leaves(moved),
                                      jax.tree.leaves(frozen)))
    taken = dispatch.taken()
    assert any(p.startswith("chunk128,heads4over2,p64,n128,state_f32,"
                            "bwd_pallas,passes")
               for p in taken["ssd_scan.plan"])
    assert set(taken["ssd_scan"]) == {"interpret"}
    # both halves of the chain round it: ops/mixer_chain.py's passes
    assert set(taken["ssd_chain"]) == {"interpret"}
    assert "relu2,ungated,k3of16,held4" in taken["ssd_moe.experts"]
    assert any(p.endswith(",operands_bshd,heads2x64")
               and "rope_in_kernel" not in p
               for p in taken["flash_attention.plan"])
    assert any(p.endswith("groups4,n192_whole")   # ONE block: none padded
               for p in taken["grouped_matmul.plan"])
    assert any(p.startswith("kept:") for p in taken["train.remat"])


def test_layout_names_and_scopes():
    """`layers/<segment>/0/<leaf>` with a leading axis of repeats (what the
    benchmark's driver reads), the logical axes beside every leaf, the
    mamba mixer under `ssm` with its chain under `ssm.chain` INSIDE it,
    attention under `attn.full`, the expert layer's norm and shared expert
    under `mlp`."""
    config = sm.SsdMoEConfig.tiny()
    shapes = jax.eval_shape(lambda: sm.init_params(config,
                                                   jax.random.PRNGKey(0)))
    axes = sm.logical_axes(config)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    tokens = jnp.asarray(_tokens(rows=1))
    text = jax.jit(lambda p: sm.loss_fn(p, {"tokens": tokens}, config)
                   ).lower(shapes).as_text(debug_info=True)
    for scope in (common.SSM, common.ATTN_FULL, common.MLP, common.MOE_ROUTE,
                  common.MOE_DISPATCH, common.MOE_EXPERTS, common.MOE_COMBINE,
                  common.LOSS):
        assert f"/{scope}/" in text, scope
    assert f"/{common.SSM}/{common.SSM_CHAIN}/" in text
    assert common.ATTN_SLIDING not in text and common.ATTN_GATE not in text


@pytest.mark.parametrize("bad", [
    {"use_conv_bias": False}, {"mlp_hidden_act": "silu"},
    {"norm_topk_prob": False}, {"tie_word_embeddings": True},
    {"n_group": 2}, {"hybrid_override_pattern": "MEM-E"},
    {"num_hidden_layers": 4}, {"first_held_expert": 14},
    {"residual_in_fp32": True}])
def test_what_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        sm.SsdMoEConfig.tiny(**bad)
