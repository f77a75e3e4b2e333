"""The compressed-convolutional-attention reference
(benchmark/reference/zaya1_cca_moe.py) on the CPU: against a token-by-token
walk of the same equations in float64 (one token at a time through every
layer, each layer keeping the previous token's normed input, projections
and first convolution, and a cache of roped keys and values: nothing of the
reference's array forms), its gradient against finite differences, against
the program at the configuration's rehearsal sizes, and its own invariants
(imports nothing of the program, the share)."""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers import train_model
from benchmark.reference import zaya1_cca_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "zaya1-8b-train-d4.json"
_erf = np.vectorize(math.erf)


def _rehearsal():
    with open(os.path.join(HERE, "..", "configs", NAME)) as f:
        doc = json.load(f)
    return doc, {**doc["model"], **doc["rehearse"]["model"]}


@pytest.fixture(scope="module")
def setup():
    from ray_tpu.models import cca_moe

    doc, model = _rehearsal()
    config = dataclasses.replace(
        train_model.build_config(doc["program"], model, doc["train"]),
        dtype=jnp.float32, fused_ce=False)
    params = cca_moe.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 49), 0, model["vocab_size"]))
    return cca_moe, config, params, tokens, ref.dims_from_config(model)


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text


def _gelu(x):
    return 0.5 * x * (1.0 + _erf(x / math.sqrt(2.0)))


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _norm(x, w, eps):
    return x / math.sqrt(float(np.mean(x * x)) + eps) * w


def _walk(params, tokens, d):
    """-log p(tokens[t+1] | tokens[:t+1]) for every t, ONE TOKEN AT A TIME,
    float64: the configuration's equations as a decoder would step them."""
    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    stacked = P["layers"]["seg00"]["0"]
    layers = [{k: v[i] for k, v in stacked.items()}
              for i in range(d["layers"])]
    heads, kv, hd, r = d["heads"], d["kv"], d["d"], d["rotary"]
    group, lq = heads // kv, heads * hd
    inv_freq = d["theta"] ** (-2.0 * np.arange(r // 2) / r)
    states = [{"h": None, "c": None, "c1": None, "k": [], "v": []}
              for _ in layers]

    def rope(x, t):             # x [n, hd]
        cos, sin = np.cos(t * inv_freq), np.sin(t * inv_freq)
        a, b = x[:, :r // 2], x[:, r // 2:r]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin,
                               x[:, r:]], axis=1)

    nll = []
    for t in range(len(tokens) - 1):
        x = P["tok_embed"][tokens[t]]
        state_r = None
        for lp, st in zip(layers, states):
            assert lp["conv0_w"].shape[0] == lp["conv1_w"].shape[1] == 2
            h = _norm(x, lp["ln1_w"], d["eps"])
            zero = np.zeros_like
            prev_h = zero(h) if st["h"] is None else st["h"]
            q0, k0 = h @ lp["wq"], h @ lp["wk"]
            v = np.concatenate([h @ lp["wv1"], prev_h @ lp["wv2"]])
            c = np.concatenate([q0, k0])
            prev_c = zero(c) if st["c"] is None else st["c"]
            c1 = lp["conv0_w"][0] * prev_c + lp["conv0_w"][1] * c \
                + lp["conv0_b"]
            prev_c1 = zero(c1) if st["c1"] is None else st["c1"]
            c2 = np.stack([
                prev_c1[j * hd:(j + 1) * hd] @ lp["conv1_w"][j, 0]
                + c1[j * hd:(j + 1) * hd] @ lp["conv1_w"][j, 1]
                for j in range(heads + kv)]) + lp["conv1_b"].reshape(-1, hd)
            st.update(h=h, c=c, c1=c1)
            q_heads, k_heads = q0.reshape(heads, hd), k0.reshape(kv, hd)
            mean_q = np.stack([(q_heads[j] + k_heads[j // group]) / 2.0
                               for j in range(heads)])
            mean_k = np.stack([mean_q[g * group:(g + 1) * group].mean(0)
                               for g in range(kv)])
            q, k = c2[:heads] + mean_q, c2[heads:] + mean_k
            q = q / np.sqrt((q * q).sum(1, keepdims=True) + 1e-6) \
                * math.sqrt(hd)
            k = k / np.sqrt((k * k).sum(1, keepdims=True) + 1e-6) \
                * math.sqrt(hd) * lp["tau"][:, None]
            st["k"].append(rope(k, t))
            st["v"].append(v.reshape(kv, hd))
            q = rope(q, t)
            keys, values = np.stack(st["k"]), np.stack(st["v"])  # [t+1, kv,]
            out = []
            for j in range(heads):
                s = keys[:, j // group] @ q[j] / math.sqrt(hd)
                p = np.exp(s - s.max())
                out.append((p / p.sum()) @ values[:, j // group])
            f = np.concatenate(out) @ lp["wo"]
            x = (lp["attn_sr"] * x + lp["attn_br"]) \
                + (lp["attn_sh"] * f + lp["attn_bh"])
            h = _norm(x, lp["ln2_w"], d["eps"])
            rr = h @ lp["router_down_w"] + lp["router_down_b"]
            if state_r is not None:
                rr = rr + lp["router_carry"] * state_r
            state_r = rr
            a = _norm(rr, lp["router_norm_w"], d["eps"])
            a = _gelu(a @ lp["router_w1"] + lp["router_b1"])
            a = _gelu(a @ lp["router_w2"] + lp["router_b2"])
            z = a @ lp["router_w3"]
            p = np.exp(z - z.max())
            p = p / p.sum()
            f = np.zeros_like(x)
            for e in np.argsort(-(p + lp["router_bias"]),
                                kind="stable")[:d["top_k"]]:
                mine = e - d["first_held"]
                if 0 <= mine < d["held"]:
                    f = f + p[e] * ((_silu(h @ lp["experts_gate"][mine])
                                     * (h @ lp["experts_up"][mine]))
                                    @ lp["experts_down"][mine])
            x = (lp["ffn_sr"] * x + lp["ffn_br"]) \
                + (lp["ffn_sh"] * f + lp["ffn_bh"])
        logits = P["tok_embed"] @ _norm(x, P["final_norm_w"], d["eps"])
        m = logits.max()
        nll.append(m + math.log(np.exp(logits - m).sum())
                   - logits[tokens[t + 1]])
    return np.asarray(nll)


def test_the_reference_is_the_token_by_token_walk(setup):
    _, _, params, tokens, dims = setup
    got = np.asarray(ref.token_nll(params, tokens[0], dims))
    want = _walk(params, tokens[0], dims)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert abs(want.mean() - math.log(256)) < 1.0


def test_the_walk_agrees_for_a_share_of_the_experts(setup):
    """Eight of sixteen held: the absent experts' terms are left out of
    both."""
    _, _, params, tokens, dims = setup
    stacked = params["layers"]["seg00"]["0"]
    share = {**params, "layers": {"seg00": {"0": {
        k: v[:, 8:] if k.startswith("experts_") else v
        for k, v in stacked.items()}}}}
    d = {**dims, "held": 8, "first_held": 8}
    got = np.asarray(ref.token_nll(share, tokens[1], d))
    np.testing.assert_allclose(got, _walk(share, tokens[1], d), atol=2e-4)
    whole = np.asarray(ref.token_nll(params, tokens[1], dims))
    assert np.abs(got - whole).max() > 1e-3


def test_the_reference_agrees_with_the_program_at_the_rehearsal_size(setup):
    model_file, config, params, tokens, dims = setup
    got = model_file.token_nll(params, {"tokens": jnp.asarray(tokens)},
                               config)
    want = ref.batch_token_nll(params, tokens, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def test_the_gradient_is_the_finite_difference(setup):
    """`Pass.grads` (a layer at a time, the router state's cotangent beside
    the stream's) against central differences of the mean NLL along the
    gradient's own direction, for the parameters the carry and the tied head reach."""
    _, _, params, tokens, dims = setup
    row = tokens[0]
    run = ref.Pass(params, row[:-1], dims, for_grads=True)
    grads = {}
    for path, g in run.grads(row[1:]):
        grads[path] = g

    def loss(p):
        return float(np.mean(np.asarray(ref.token_nll(p, row, dims),
                                        np.float64)))

    for path, name in [(("tok_embed",), None),
                       (("layers", "seg00", "0", 0), "router_down_w"),
                       (("layers", "seg00", "0", 2), "router_carry"),
                       (("layers", "seg00", "0", 1), "conv1_w"),
                       (("layers", "seg00", "0", 3), "tau")]:
        g = np.asarray(grads[path] if name is None else grads[path][name])
        # along the gradient itself: the steepest slope there is stands
        # furthest above float32's rounding and a flipped selection's jump
        direction = g / np.linalg.norm(g)

        def moved(eps):
            if name is None:
                return {**params, path[0]: params[path[0]]
                        + eps * direction}
            stacked = dict(params["layers"]["seg00"]["0"])
            stacked[name] = stacked[name].at[path[3]].add(eps * direction)
            return {**params, "layers": {"seg00": {"0": stacked}}}

        eps = 1e-3
        fd = (loss(moved(eps)) - loss(moved(-eps))) / (2 * eps)
        want = float(np.sum(g * direction))
        assert abs(fd - want) <= 0.05 * abs(want) + 2e-4, (path, name, fd,
                                                           want)
