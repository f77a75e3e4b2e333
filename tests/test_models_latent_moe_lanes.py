"""models/latent_moe.py in the form Xing4.0-29B-A4B publishes (a query
latent, yarn, a residual stream of four lanes, a second loss from a
multi-token-prediction block) at tiny widths, kernels interpreted on the CPU,
against benchmark/reference/xing4_hc_mla_moe.py on seeded weights.  The
one-lane form (kanana's) is tests/test_models_latent_moe.py, whose small
helpers this file takes."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from attention_cases import _pallas_calls
from benchmark.reference import xing4_hc_mla_moe as xref
from ray_tpu.models import latent_moe as lm, stack
from test_models_latent_moe import (  # noqa: F401 (the fixture is autouse)
    _f32, _interpret_mode, _nll, _tokens)

YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16}
XING = dict(q_lora_rank=24, rope_scaling=YARN, rope_theta=10000.0,
            n_shared_experts=1, routed_scaling_factor=2.0, hc_mult=4,
            num_nextn_predict_layers=1)


def _xdims(config):
    model = {f.name: getattr(config, f.name)
             for f in dataclasses.fields(config)}
    return xref.dims_from_config({**model, "rope_scaling": dict(
        config.rope_scaling) if config.rope_scaling else None})


def _walk(got, path):
    for key in path:
        got = got[key]
    return got


def test_a_query_latent_matches_the_reference():
    config = _f32(q_lora_rank=24)
    params = lm.init_params(config, jax.random.PRNGKey(21))
    assert params["layers"]["seg00"]["0"]["wq_a"].shape == (1, 64, 24)
    assert "wq" not in params["layers"]["seg00"]["0"]
    tokens = _tokens(rows=1)
    got = _nll(params, {"tokens": jnp.asarray(tokens)}, config)
    want = xref.batch_token_nll(params, tokens, _xdims(config))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_yarn_tables_are_hugging_faces_arithmetic():
    """`_compute_yarn_parameters` written out: the correction range from
    beta_fast / beta_slow over the original length, the linear ramp, the
    interpolated and the published frequencies mixed; the factor in cos and
    sin is mscale / mscale_all_dim's, the softmax scale's is squared."""
    import math

    config = lm.LatentMoEConfig(
        rope_scaling={**YARN, "original_max_position_embeddings": 4096},
        rope_theta=10000.0, qk_rope_head_dim=64, num_hidden_layers=1)
    dim, base, factor, original = 64, 10000.0, 64.0, 4096

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(32)), 0)
    high = min(math.ceil(correction_dim(1)), dim - 1)
    assert (low, high) == (10, 23)
    pos = base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq = (1 / (factor * pos)) * ramp + (1 / pos) * (1 - ramp)
    cos, sin = lm.rope_tables(128, config)
    angle = np.arange(128)[:, None] * inv_freq[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(angle), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(angle), atol=2e-5)
    np.testing.assert_allclose(xref.yarn_inv_freq(_xdims(config)), inv_freq,
                               rtol=1e-6)
    assert config.softmax_scale == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192))
    assert config.softmax_scale == pytest.approx(2.00474 / 13.8564, rel=1e-5)
    assert lm.LatentMoEConfig().softmax_scale == 1 / math.sqrt(192)
    hash(config)        # the config is a cache key: the dict is frozen


@pytest.fixture(scope="module")
def xing():
    """The tiny Xing form in float32, its weights, one row of tokens and the
    reference's pass over it."""
    config = lm.LatentMoEConfig.tiny(dtype=jnp.float32, remat=False, **XING)
    params = lm.init_params(config, jax.random.PRNGKey(22))
    tokens = _tokens(rows=1, seed=23)
    run = xref.Pass(params, tokens[0, :-1], _xdims(config), for_grads=True)
    return config, params, tokens, run


def test_lanes_and_the_second_loss_match_the_reference(xing):
    """Every position's objective, nll_main + 0.3 nll_mtp with the block's
    loss zero at the last position, and its two parts."""
    config, params, tokens, run = xing
    batch = {"tokens": jnp.asarray(tokens)}
    got = _nll(params, batch, config)
    want = run.token_nll(tokens[0, 1:])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=3e-4)
    loss, metrics = jax.jit(lm.loss_and_metrics, static_argnums=2)(
        params, batch, config)
    second = run.mtp_nll(tokens[0, 1:])
    assert float(second[-1]) == 0 and float(second[:-1].min()) > 0
    assert float(metrics["mtp_nll"]) == pytest.approx(
        float(second.sum()) / (len(second) - 1), rel=1e-4)
    assert float(loss) == pytest.approx(float(want.mean()), rel=1e-5)
    main = _nll(params, batch, dataclasses.replace(
        config, num_nextn_predict_layers=0))
    np.testing.assert_allclose(
        np.asarray(got[0] - main[0]), 0.3 * np.asarray(second), atol=3e-4)
    assert 0 < float(metrics["hc_row_err"]) < 0.1
    assert {"moe_rows_held", "moe_load_max"} <= set(metrics)


def _loss_grad(xing, config):
    """jax.grad of the program's mean objective on the fixture's weights and
    row, under `config`."""
    _, params, tokens, _ = xing
    return jax.jit(jax.grad(lambda p: lm.loss_fn(
        p, {"tokens": jnp.asarray(tokens)}, config)))(params)


@pytest.fixture(scope="module")
def plain_grad(xing):
    """The gradient with no checkpoint anywhere (the fixture's own config):
    the reference's case and the lowest rung's share the one compile."""
    with pytest.MonkeyPatch.context() as mp:    # module scope: before autouse
        mp.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        return _loss_grad(xing, xing[0])


def test_lanes_and_the_second_loss_gradient_matches_the_reference(
        xing, plain_grad):
    """jax.grad of the program's mean objective against the reference's
    walked back a sublayer at a time: the stack, the collapse, the block's
    group "mtp" and the embedding and the head, which both losses reach."""
    config, params, tokens, run = xing
    got = plain_grad
    seen = set()
    for path, grad in run.grads(tokens[0, 1:]):
        for name, want in (grad.items() if isinstance(grad, dict)
                           else [(None, grad)]):
            g = _walk(got, path) if name is None \
                else got["layers"][path[1]][path[2]][name][path[3]]
            want, g = np.asarray(want), np.asarray(g)
            seen.add(path + (name,))
            if name == "router_bias" or path[-1] == "router_bias":
                assert not want.any() and not g.any()
                continue
            assert np.linalg.norm(g - want) \
                <= 3e-4 * np.linalg.norm(want) + 1e-7, (path, name)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert len(seen) == sum(
        leaf.shape[0] if path[0].key == "layers" else 1
        for path, leaf in leaves)
    assert ("mtp", "w_eh", None) in seen and ("hc_head", "w", None) in seen
    assert ("mtp", "hc_head", "scale", None) in seen


@pytest.fixture(scope="module")
def lowest_rung(xing):
    """ONE pass for the two cases below, on the ladder's lowest rung
    (`remat_policy="full"`: a layer's checkpoint keeps nothing).  name ->
    (the loss's gradient, how often the jaxpr of ONE expert layer's vjp under
    the layer's checkpoint calls the flash forward and the flash backward,
    what the traces left under `latent_moe.attention_checkpoint`), for the
    program "as_is" and for the "bare" form it had before PR 55, attention's
    inner checkpoint keeping nothing: `SAVE_ATTN_NAMES` emptied, and
    `stack.layer_fn`'s cache emptied round it, since `jax.checkpoint` finds a
    layer's trace again by the function's identity."""
    from ray_tpu.models import common
    from ray_tpu.ops import dispatch

    base, params, _, _ = xing
    config = dataclasses.replace(base, remat=True, remat_policy="full")
    lp = jax.tree.map(lambda a: a[0], params["layers"]["seg01"]["0"])
    x = jax.random.normal(jax.random.PRNGKey(30),
                          (1, 128, config.hc_mult * config.hidden_size))
    tables = lm._tables(128, config)

    def rung():
        layer = stack.layer_fn(lm._layer, "moe", config)
        before = dispatch.taken().get("latent_moe.attention_checkpoint", {})
        vjp = jax.make_jaxpr(jax.grad(
            lambda x, lp: jnp.sum(layer(x, lp, tables)[0] ** 2),
            argnums=(0, 1)))(x, lp).jaxpr
        grad = _loss_grad(xing, config)
        after = dispatch.taken()["latent_moe.attention_checkpoint"]
        kernels = [call.params["name"] for call in _pallas_calls(vjp)]
        return (grad, kernels.count("flash_fwd"), kernels.count("flash_bwd"),
                {k: n - before.get(k, 0) for k, n in after.items()
                 if n > before.get(k, 0)})

    out = {}
    with pytest.MonkeyPatch.context() as mp:    # module scope: before autouse
        mp.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        stack.layer_fn.cache_clear()
        out["as_is"] = rung()
        mp.setattr(common, "SAVE_ATTN_NAMES", ())
        stack.layer_fn.cache_clear()
        out["bare"] = rung()
    stack.layer_fn.cache_clear()
    return out


def test_the_lowest_rung_keeps_the_gradients_bits(plain_grad, lowest_rung):
    """Out and lse kept across attention's inner checkpoint are the kernel's
    own outputs: the gradient is, leaf for leaf and bit for bit, the one the
    bare inner checkpoint gave.  Against NO checkpoint it is float32's
    rounding away and no bit for bit: XLA's CPU fusions differ between the
    two programs (the bare form reads the same 1.6e-6 of a leaf's largest
    entry)."""
    got, bare = lowest_rung["as_is"][0], lowest_rung["bare"][0]
    assert jax.tree.structure(got) == jax.tree.structure(bare) \
        == jax.tree.structure(plain_grad)
    for (path, g), b, plain in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree.leaves(bare), jax.tree.leaves(plain_grad)):
        g, plain = np.asarray(g), np.asarray(plain)
        assert np.array_equal(g, np.asarray(b)), path
        assert np.abs(g - plain).max() <= 1e-5 * np.abs(plain).max(), path
    assert len(jax.tree.leaves(got)) == 77


def test_the_lowest_rung_runs_the_flash_forward_twice_a_layer(xing,
                                                              lowest_rung):
    """A layer's vjp under the layer's checkpoint: the forward, the layer's
    remat, and at attention's backward NO third flash forward (the bare inner
    checkpoint's); `dispatch.taken()` says that the mechanism engaged, once a
    kind of layer.  One lane, or no remat: the branch is not taken."""
    from ray_tpu.ops import dispatch

    _, forwards, backwards, taken = lowest_rung["as_is"]
    assert (forwards, backwards) == (2, 1)
    # a trace a kind of layer: the vjp's is the loss's expert layers' too
    assert taken == {"kept:attn_out,attn_lse": 2}
    assert lowest_rung["bare"][1:] == (3, 1, {"kept:": 2})
    before = dispatch.taken()["latent_moe.attention_checkpoint"]
    config, params, _, _ = xing
    one_lane = dataclasses.replace(_f32(), remat=True, remat_policy="full")
    tokens = jnp.zeros((1, 128), jnp.int32)
    for c, leaves in ((config, params), (one_lane, lm.init_params(
            one_lane, jax.random.PRNGKey(31)))):
        jax.make_jaxpr(lambda p: lm._stream(p, tokens, c)[0])(leaves)
    assert dispatch.taken()["latent_moe.attention_checkpoint"] == before


def test_the_probe_runs_the_lanes_alone_on_the_references_operands(xing):
    config, params, tokens, run = xing
    operands, want = run.residual_mix()
    assert operands[0].dtype == jnp.bfloat16 and operands[0].shape == (
        1, 128, 4 * config.hidden_size)
    got = lm.residual_mix(*operands, config=dataclasses.replace(
        config, dtype=jnp.bfloat16))
    err = float(jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want ** 2)))
    # float32 out of both calls: no rounding of the stream's hides comb's
    assert got.dtype == jnp.float32 and err < 2e-5


def test_zero_lane_weights_are_the_one_lane_model():
    """With every w_hc and the collapse's weight zero and the draws of base,
    equal lanes stay equal: the model IS x + f(norm(x)) on the same
    weights."""
    lanes = _f32(hc_mult=4)
    plain = _f32()
    params = lm.init_params(lanes, jax.random.PRNGKey(24))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if path[-1].key in ("hc_attn_w", "hc_ffn_w")
        or [k.key for k in path[-2:]] == ["hc_head", "w"] else a,
        params)
    bare = {k: v for k, v in params.items() if not k.startswith("hc_")}
    bare["layers"] = {
        seg: {"0": {k: v for k, v in leaves["0"].items()
                    if not k.startswith("hc_")}}
        for seg, leaves in params["layers"].items()}
    tokens = {"tokens": jnp.asarray(_tokens(rows=1, seed=25))}
    got = _nll(params, tokens, lanes)
    want = _nll(bare, tokens, plain)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_the_shares_add_up_with_the_lanes_on():
    """Eight chips, two of sixteen experts each, the lanes on: the eight
    shares' feed-forward sublayers round the stream (the program's), with
    the lanes' comb term and the shared expert counted ONCE, are the uncut
    reference's sublayer."""
    config = _f32(n_routed_experts=16, router_width=16, hc_mult=4,
                  n_shared_experts=1)
    lp = jax.tree.map(
        lambda a: a[0], lm.init_params(config, jax.random.PRNGKey(26))
        ["layers"]["seg01"]["0"])
    x = jax.random.normal(jax.random.PRNGKey(27), (1, 96, 4 * 64))
    d = _xdims(config)
    X = x[0].reshape(96, 4, 64)
    once = xref.ffn_round(X, lp, d, (0, 16), parts=("lanes", "shared"))
    lanes_alone = xref.ffn_round(X, lp, d, (0, 16), parts=("lanes",))
    total, rows = once, 0
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        c = dataclasses.replace(config, n_routed_experts=2,
                                first_held_expert=2 * share)
        counts = []

        def routed(u, c=c, held=held):
            y = lm.rms_norm(u, lp["ln2_w"], c.rms_norm_eps)
            out, stats = lm._routed_part(
                y.reshape(-1, 64), lp["router_w"], lp["router_bias"],
                lp["experts_gate"][held], lp["experts_up"][held],
                lp["experts_down"][held], c)
            counts.append(int(stats["rows_held"]))
            return out.reshape(y.shape)

        out = stack.residual(x, routed, lp, "hc_ffn", c)
        total = total + out[0].reshape(96, 4, 64) - lanes_alone
        rows += counts[0]
    assert rows == 96 * config.num_experts_per_tok      # every assignment
    want = xref.ffn_round(X, lp, d, (0, 16))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=3e-5, rtol=1e-5)


def test_the_kernels_run_inside_the_model_interpreted():
    """At a lane of 128 columns in bfloat16 the lanes' calls take the
    kernels (interpreted here), and the loss stays the reference's."""
    from ray_tpu.ops import dispatch

    config = lm.LatentMoEConfig.tiny(
        hidden_size=128, num_hidden_layers=2, remat=False, **{
            **XING, "num_nextn_predict_layers": 0})
    params = lm.init_params(config, jax.random.PRNGKey(28))
    tokens = _tokens(rows=1, seed=29)
    before = dispatch.taken().get("hyper_connection", {}).get("interpret", 0)
    got = lm.token_nll(params, {"tokens": jnp.asarray(tokens)}, config)
    assert dispatch.taken()["hyper_connection"]["interpret"] >= before + 9
    want = xref.batch_token_nll(params, tokens, _xdims(config))
    assert abs(float(got.mean()) - float(want.mean())) < 0.02
    assert float(jnp.sqrt(jnp.mean((got - want) ** 2))) < 0.15
