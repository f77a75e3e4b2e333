"""benchmark/reference/xing4_hc_mla_moe.py against things written out here:
the Sinkhorn projection against a loop over single numbers, the lanes at
zero weights against the plain residual, the yarn arithmetic's two ends, the
second loss's mask, and the reference's own gradient against jax.grad of its
whole forward."""

import math

import numpy as np

import jax
import jax.numpy as jnp

from benchmark.reference import xing4_hc_mla_moe as ref

MODEL = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=2, first_k_dense_replace=1,
    num_attention_heads=2, kv_lora_rank=16, q_lora_rank=12,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, router_width=8, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.0, rms_norm_eps=1e-6,
    rope_theta=10000.0, rope_scaling={
        "type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, num_nextn_predict_layers=1, mtp_loss_weight=0.3)


def _params(seed=0, lanes=True):
    from ray_tpu.models import latent_moe as lm     # the layout's only

    config = lm.LatentMoEConfig(**{
        **{k: v for k, v in MODEL.items() if k != "mtp_loss_weight"},
        "hc_mult": 4 if lanes else None}, dtype=jnp.float32)
    return lm.init_params(config, jax.random.key(seed))


def test_sinkhorn_by_single_numbers():
    rng = np.random.default_rng(0)
    c = rng.standard_normal((4, 4)) * 2
    m = np.exp(c - c.max(1, keepdims=True))
    m = m / m.sum(1, keepdims=True) + 1e-6
    for j in range(4):
        m[:, j] = m[:, j] / (m[:, j].sum() + 1e-6)
    for _ in range(19):
        for i in range(4):
            m[i] = m[i] / (m[i].sum() + 1e-6)
        for j in range(4):
            m[:, j] = m[:, j] / (m[:, j].sum() + 1e-6)
    got = np.asarray(ref.sinkhorn(jnp.asarray(c, jnp.float32), 20, 1e-6))
    np.testing.assert_allclose(got, m, rtol=2e-5)
    np.testing.assert_allclose(got.sum(0), 1.0, atol=1e-5)
    one = np.asarray(ref.sinkhorn(jnp.asarray(c, jnp.float32), 1, 1e-6))
    assert np.abs(one.sum(1) - 1).max() > 0.05


def test_yarn_ends_and_scale():
    d = ref.dims_from_config({**MODEL, "qk_rope_head_dim": 64,
                              "qk_nope_head_dim": 128, "rope_scaling": {
        **MODEL["rope_scaling"], "original_max_position_embeddings": 4096}})
    inv = ref.yarn_inv_freq(d)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)     # fast
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)  # slow
    assert ref.softmax_scale(d) == (0.1 * math.log(64) + 1) ** 2 \
        / math.sqrt(192)


def test_zero_lane_weights_are_the_plain_residual():
    params = _params()
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if path[-1].key in ("hc_attn_w", "hc_ffn_w")
        or [k.key for k in path[-2:]] == ["hc_head", "w"] else a, params)
    tokens = np.arange(33) % 64
    dims = ref.dims_from_config({**MODEL, "num_nextn_predict_layers": 0})
    got = ref.token_nll(params, tokens, dims)
    want = ref.token_nll(params, tokens, {**dims, "lanes": 0})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_the_second_loss_is_masked_and_weighted():
    params, dims = _params(1), ref.dims_from_config(MODEL)
    tokens = (np.arange(34) * 7) % 64
    run = ref.Pass(params, tokens[:-1], dims)
    second = np.asarray(run.mtp_nll(tokens[1:]))
    assert second[-1] == 0 and (second[:-1] > 0).all()
    main = np.asarray(ref.Pass(params, tokens[:-1], {**dims, "mtp": 0})
                      .token_nll(tokens[1:]))
    np.testing.assert_allclose(np.asarray(run.token_nll(tokens[1:])),
                               main + 0.3 * second, rtol=1e-6)
    # the block reads the token one on and scores the token two on: moving
    # the LAST target moves only the main loss's last position
    moved = tokens.copy()
    moved[-1] = (moved[-1] + 1) % 64
    other = np.asarray(ref.Pass(params, moved[:-1], dims).mtp_nll(moved[1:]))
    np.testing.assert_allclose(other[:-2], second[:-2], rtol=1e-6)


def test_grads_are_jax_grad_of_the_whole_forward():
    params, dims = _params(2), ref.dims_from_config(MODEL)
    tokens = (np.arange(34) * 5 + 3) % 64
    want = jax.grad(lambda p: jnp.mean(ref.token_nll(p, tokens, dims)))(params)
    run = ref.Pass(params, tokens[:-1], dims, for_grads=True)
    seen = 0
    for path, grad in run.grads(tokens[1:]):
        for name, g in (grad.items() if isinstance(grad, dict)
                        else [(None, grad)]):
            w = want
            for key in (path if name is None else path[:3]):
                w = w[key]
            w = np.asarray(w if name is None else w[name][path[3]])
            assert np.linalg.norm(np.asarray(g) - w) \
                <= 2e-4 * np.linalg.norm(w) + 1e-7, (path, name)
            seen += 1
    assert seen == sum(
        leaf.shape[0] if path[0].key == "layers" else 1
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0])


def test_the_probe_is_the_round_about_the_identity():
    params, dims = _params(3), ref.dims_from_config(MODEL)
    run = ref.Pass(params, np.arange(32) % 64, dims)
    (X, w, scale, base), out = run.residual_mix()
    assert X.dtype == jnp.bfloat16 and X.shape == out.shape == (1, 32, 128)
    lanes = X[0].astype(jnp.float32).reshape(32, 4, 32)
    pre, post, comb = ref.lane_mix(lanes, w, scale, base, dims)
    u = jnp.einsum("ti,tid->td", pre, lanes)
    want = post[:, :, None] * u[:, None] + jnp.einsum("tji,tid->tjd", comb,
                                                      lanes)
    np.testing.assert_allclose(np.asarray(out[0]).reshape(32, 4, 32),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
