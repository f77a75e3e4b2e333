"""The Mamba-2 / attention / routed-expert reference
(benchmark/reference/nemotron_h_ssd_moe.py) on the CPU: its `lax.scan` and
array forms against a Python loop over tokens of the same equations in
float64 (one token at a time through a mamba layer, with the conv's three-
token history and the state kept by hand; attention a token at a time over
a cache of keys and values; the experts a token at a time), and its own
invariants (imports nothing of the program, the share)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h_ssd_moe as ref

MODEL = {"hybrid_override_pattern": "ME*", "num_hidden_layers": 3,
         "mamba_num_heads": 4, "mamba_head_dim": 4, "n_groups": 2,
         "ssm_state_size": 6, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 4,
         "layer_norm_epsilon": 1e-5, "num_experts_per_tok": 2,
         "routed_scaling_factor": 2.5, "n_routed_experts": 3,
         "first_held_expert": 1, "router_width": 6}
HIDDEN, T = 8, 70


def _weights(seed=0):
    rng = np.random.default_rng(seed)
    n = lambda *s: rng.normal(size=s) / math.sqrt(s[-2] if len(s) > 1 else 1)
    inner, wide = 16, 16 + 2 * 2 * 6
    mamba = {"ln_w": 1 + 0.1 * n(HIDDEN), "w_in": n(HIDDEN, inner + wide + 4),
             "conv_w": n(4, wide), "conv_b": 0.1 * n(wide),
             "A_log": np.log(rng.uniform(1, 16, 4)), "D": np.ones(4),
             "dt_bias": rng.normal(size=4) - 3, "gn_w": 1 + 0.1 * n(inner),
             "w_out": n(inner, HIDDEN)}
    full = {"ln_w": 1 + 0.1 * n(HIDDEN), "wq": n(HIDDEN, 16),
            "wk": n(HIDDEN, 8), "wv": n(HIDDEN, 8), "wo": n(16, HIDDEN)}
    experts = {"ln_w": 1 + 0.1 * n(HIDDEN), "router_w": n(HIDDEN, 6),
               "router_bias": 0.3 * n(6), "experts_up": n(3, HIDDEN, 5),
               "experts_down": n(3, 5, HIDDEN), "shared_up": n(HIDDEN, 7),
               "shared_down": n(7, HIDDEN)}
    return mamba, experts, full


def _norm(x, w, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _mamba_by_tokens(xs, lp):
    H, P, G, N, inner = 4, 4, 2, 6, 16
    S = np.zeros((H, P, N))
    history = np.zeros((3, inner + 2 * G * N))       # zero before the row
    a, out = -np.exp(lp["A_log"]), []
    for x_t in xs:
        proj = _norm(x_t, lp["ln_w"]) @ lp["w_in"]
        z, xBC, dt = proj[:inner], proj[inner:-H], proj[-H:]
        taps = np.vstack([history, xBC])
        history = taps[1:]
        xBC = _silu(lp["conv_b"] + (taps * lp["conv_w"]).sum(0))
        x = xBC[:inner].reshape(H, P)
        B = xBC[inner:inner + G * N].reshape(G, N)
        C = xBC[inner + G * N:].reshape(G, N)
        dt = np.log1p(np.exp(dt + lp["dt_bias"]))
        y = np.zeros((H, P))
        for h in range(H):
            g = h // (H // G)
            S[h] = math.exp(dt[h] * a[h]) * S[h] + dt[h] * np.outer(x[h], B[g])
            y[h] = S[h] @ C[g] + lp["D"][h] * x[h]
        y = y.reshape(-1) * _silu(z)
        y = _norm(y.reshape(G, -1), lp["gn_w"].reshape(G, -1)).reshape(-1)
        out.append(x_t + y @ lp["w_out"])
    return np.stack(out)


def _attention_by_tokens(xs, lp):
    keys, values, out = [], [], []
    for x_t in xs:
        u = _norm(x_t, lp["ln_w"])
        q = (u @ lp["wq"]).reshape(4, 4)
        keys.append((u @ lp["wk"]).reshape(2, 4))
        values.append((u @ lp["wv"]).reshape(2, 4))
        heads = []
        for j in range(4):
            k = np.stack([k_[j // 2] for k_ in keys])
            v = np.stack([v_[j // 2] for v_ in values])
            s = k @ q[j] / 2.0
            p = np.exp(s - s.max())
            heads.append(p / p.sum() @ v)
        out.append(x_t + np.concatenate(heads) @ lp["wo"])
    return np.stack(out)


def _experts_by_tokens(xs, lp):
    out = []
    for x_t in xs:
        u = _norm(x_t, lp["ln_w"])
        s = 1 / (1 + np.exp(-(u @ lp["router_w"])))
        sel = np.argsort(-(s + lp["router_bias"]), kind="stable")[:2]
        gates = s[sel] / (s[sel].sum() + 1e-20) * 2.5
        f = np.maximum(u @ lp["shared_up"], 0) ** 2 @ lp["shared_down"]
        for e, g in zip(sel, gates):
            if 1 <= e < 4:              # experts 1-3 are held here
                f = f + g * (np.maximum(u @ lp["experts_up"][e - 1], 0) ** 2
                             @ lp["experts_down"][e - 1])
        out.append(x_t + f)
    return np.stack(out)


@pytest.mark.parametrize("kind,walk", [
    ("M", _mamba_by_tokens), ("E", _experts_by_tokens),
    ("*", _attention_by_tokens)])
def test_a_layer_is_the_walk_over_tokens(kind, walk):
    lp = dict(zip("ME*", _weights()))[kind]
    xs = np.random.default_rng(1).normal(size=(T, HIDDEN))
    got = ref._layer(jnp.asarray(xs, jnp.float32),
                     jax.tree.map(jnp.asarray, lp), kind=kind,
                     dims=ref._pairs(ref.dims_from_config(MODEL)))
    np.testing.assert_allclose(np.asarray(got), walk(xs, lp), atol=2e-4)


def test_the_probe_gives_the_scan_s_operands_and_its_output():
    mamba, experts, full = _weights()
    params = {"tok_embed": np.random.default_rng(2).normal(size=(11, HIDDEN)),
              "lm_head": np.random.default_rng(3).normal(size=(11, HIDDEN)),
              "final_norm_w": np.ones(HIDDEN),
              "layers": {f"seg0{i}": {"0": jax.tree.map(lambda a: a[None], lp)}
                         for i, lp in enumerate((mamba, experts, full))}}
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    tokens = np.arange(T) % 11
    run = ref.Pass(params, tokens, ref.dims_from_config(MODEL),
                   for_grads=True)
    (x, dt, a, B, C, D), y = run.ssd_scan()
    assert x.shape == (1, T, 4, 4) and dt.shape == (1, T, 4) \
        and B.shape == C.shape == (1, T, 2, 6) and y.shape == x.shape
    assert bool(jnp.all(dt > 0)) and bool(jnp.all(a < 0))
    nll = run.token_nll((tokens + 1) % 11)
    assert nll.shape == (T,) and bool(jnp.all(jnp.isfinite(nll)))
    paths = [p for p, _ in run.grads((tokens + 1) % 11)]
    assert paths[0] == ("lm_head",) and paths[-1] == ("tok_embed",)
    assert [p[1] for p in paths[2:-1]] == ["seg02", "seg01", "seg00"]


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "ray_tpu" not in text.split('"""', 2)[2]
    assert "pallas" not in text.split('"""', 2)[2]
