"""Plain reference of the hyper-connected latent-attention, routed-expert
decoder with a multi-token-prediction block (Xing4.0-29B-A4B: `model_type:
xing4_0`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, nothing imported from
the program's `models/` or `ops/`.  What this model shares with the plain
DeepSeek-V3 form (the sigmoid router and the held experts' sum an expert at a
time, SwiGLU, the loss a block of rows at a time, the parameter layout's
walk) is benchmark/reference/deepseek_v3_mla_moe.py's; attention (a query
latent, yarn), the lanes and the second loss are written here.  `Pass.grads`
is the forward walked back one SUBLAYER at a time (each sublayer's
`jax.vjp`: a stream of four float32 lanes of 8192 tokens is 470 MB, and a
whole layer's residuals would not fit beside a train state).  It reads the
program's parameter LAYOUT so that it can be handed the program's weights.

The equations (T tokens, n = `hc_mult` lanes, d = `hidden_size`; no bias):

  lanes      X [T, n, d], every lane the embedding at first.  Round a
             sublayer F (attention or feed-forward) with its own float32
             leaves w [n d, 2 n + n^2], scale [3], base [2 n + n^2]:
               xbar = vec(X); r = rsqrt(mean(xbar^2) + rms_norm_eps);
               m = (xbar w) r;
               pre  = sigmoid(scale_0 m[:n] + base[:n]) + hc_eps
               post = 2 sigmoid(scale_1 m[n:2n] + base[n:2n])
               C    = clip(scale_2 m[2n:] + base[2n:], clamp_min, clamp_max)
                      as [n, n]
               comb = Sinkhorn(C): M = softmax(rows of C) + hc_eps; M /=
                      column sums + hc_eps; then `hc_sinkhorn_iters` - 1
                      times: M /= row sums + hc_eps; M /= column sums + hc_eps
               u = sum_i pre_i X_i;  y = F(RMSNorm(u));
               X'_j = post_j y + sum_i comb_ji X_i
             Behind the last layer x = sum_i (sigmoid(scale_h (xbar w_head) r
             + base_h) + hc_eps)_i X_i, then the final RMSNorm and the head.
  attention  q = RMSNorm(u W_qa) W_qb -> [T, heads, nope + rope]; c = u
             W_kva; c_kv = RMSNorm(c[:rank]); k_pe = c[rank:], one head
             shared by all; c_kv W_kvb -> [T, heads, nope + v] = [k_nope |
             v]; rope (interleaved pairs (2i, 2i + 1)) on q_pe and k_pe with
             yarn's inverse frequencies (Hugging Face's
             `_compute_yarn_parameters`: beta_fast 32, beta_slow 1, factor
             64, original 4096), cos and sin times mscale(factor, mscale) /
             mscale(factor, mscale_all_dim) = 1; causal softmax(q k^T x
             mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope)) v; W_o.
             mscale(f, m) = 0.1 m ln f + 1.
  FFN        as the DeepSeek-V3 reference: dense SwiGLU in the first
             `first_k_dense_replace` layers; then sigmoid scores, top k of
             score + bias, gates normalised x `routed_scaling_factor`, the
             HELD experts' terms summed, one shared SwiGLU.
  second loss  h'_i = [RMSNorm(embed(t_{i+1})) ; RMSNorm(x_i)] W_eh (x_i the
             collapsed stream BEFORE the final norm); lanes h' again; ONE
             expert layer with its own leaves; its own collapse and final
             norm; the SHARED embedding and head; nll_mtp[i] = -log
             p(t_{i+2}), zero at the last position.  A position's objective
             is nll_main[i] + `mtp_loss_weight` x nll_mtp[i].

Departures from the published description: none known; what could not be
confirmed offline is under `assumed` in the configuration.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v3_mla_moe import (
    HEAD_GROUP, LOGIT_ROWS, QUERY_BLOCK, _held_experts_sum, _layer_params,
    _layers_in_order, _rms_norm, _rows_nll, _swiglu)
from benchmark.reference.deepseek_v3_mla_moe import \
    dims_from_config as _base_dims

F32 = jnp.float32


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group: the DeepSeek-V3 reference's, the query latent, yarn's numbers,
    the lanes' and the second loss's."""
    yarn = model.get("rope_scaling") or {}
    return {
        **_base_dims(model),
        "q_rank": int(model.get("q_lora_rank") or 0),
        "yarn_factor": float(yarn.get("factor", 0.0)),
        "yarn_original": float(yarn.get(
            "original_max_position_embeddings", 0)),
        "yarn_beta_fast": float(yarn.get("beta_fast", 32)),
        "yarn_beta_slow": float(yarn.get("beta_slow", 1)),
        "yarn_mscale": float(yarn.get("mscale", 1)),
        "yarn_mscale_all_dim": float(yarn.get("mscale_all_dim", 0)),
        "lanes": int(model.get("hc_mult") or 0),
        "hc_iters": int(model.get("hc_sinkhorn_iters", 20)),
        "hc_eps": float(model.get("hc_eps", 1e-6)),
        "clamp_min": float(model.get("mhc_h_res_clamp_min", -30)),
        "clamp_max": float(model.get("mhc_h_res_clamp_max", 30)),
        "mtp": int(model.get("num_nextn_predict_layers") or 0),
        "mtp_weight": float(model.get("mtp_loss_weight", 0.3)),
    }


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(d: dict) -> np.ndarray:
    """Hugging Face's `_compute_yarn_parameters` over the `rope` rotary
    dimensions: [rope / 2]."""
    dim, base, factor = d["rope"], d["theta"], d["yarn_factor"]
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not factor:
        return (1.0 / pos).astype(np.float32)

    def correction_dim(rotations):
        return dim * math.log(d["yarn_original"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(d["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(d["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    return ((1.0 / (factor * pos)) * (1 - extrapolation)
            + (1.0 / pos) * extrapolation).astype(np.float32)


def _rope(x, d):
    """x [T, heads, rope]: pairs (2i, 2i + 1) turned by position x the
    inverse frequency i, cos and sin times yarn's factor."""
    T = x.shape[0]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(d))
    factor = 1.0
    if d["yarn_factor"]:
        factor = _mscale(d["yarn_factor"], d["yarn_mscale"]) / _mscale(
            d["yarn_factor"], d["yarn_mscale_all_dim"] or 1.0)
    cos, sin = (jnp.cos(angle) * factor).astype(x.dtype)[:, None], \
        (jnp.sin(angle) * factor).astype(x.dtype)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def softmax_scale(d: dict) -> float:
    scale = 1.0 / math.sqrt(d["nope"] + d["rope"])
    if d["yarn_factor"] and d["yarn_mscale_all_dim"]:
        scale *= _mscale(d["yarn_factor"], d["yarn_mscale_all_dim"]) ** 2
    return scale


def _causal_attention(q, k, v, scale):
    """q, k [T, heads, d], v [T, heads, e] -> [T, heads, e]; query t sees
    keys 0 .. t; a block of queries and a group of heads at a time."""
    T, heads, dq = q.shape
    block, group = min(QUERY_BLOCK, T), min(HEAD_GROUP, heads)
    pad = -T % block
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2).reshape(
        heads // group, group, T + pad, dq)
    kg = k.transpose(1, 0, 2).reshape(heads // group, group, T, dq)
    vg = v.transpose(1, 0, 2).reshape(heads // group, group, T, -1)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def one_block(start):
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]

        @jax.checkpoint
        def one_group(args):
            qj, kj, vj = args
            scores = jnp.einsum("hqd,hkd->hqk", qj, kj) * scale
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,hke->hqe", probs, vj)

        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=2)
        return jax.lax.map(one_group, (qb, kg, vg))

    a = jax.lax.map(one_block, jnp.arange(0, T + pad, block))
    return a.transpose(0, 3, 1, 2, 4).reshape(T + pad, heads, -1)[:T]


def _attention(u, lp, d):
    T = u.shape[0]
    heads, nope, rope, rank = d["heads"], d["nope"], d["rope"], d["rank"]
    if d["q_rank"]:
        q = _rms_norm(u @ lp["wq_a"], lp["q_norm_w"], d["eps"]) @ lp["wq_b"]
    else:
        q = u @ lp["wq"]
    q = q.reshape(T, heads, nope + rope)
    latent = u @ lp["wkv_a"]
    c_kv = _rms_norm(latent[:, :rank], lp["kv_norm_w"], d["eps"])
    kv = (c_kv @ lp["wkv_b"]).reshape(T, heads, nope + d["v"])
    k_pe = _rope(latent[:, None, rank:], d)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], d)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (T, heads, rope))], axis=-1)
    a = _causal_attention(q, k, kv[..., nope:], softmax_scale(d))
    return a.reshape(T, heads * d["v"]) @ lp["wo"]


def _feed_forward(h, lp, kind, d):
    if kind == "dense":
        return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    routed = _held_experts_sum(
        h, lp["router_w"], lp["router_bias"], lp["experts_gate"],
        lp["experts_up"], lp["experts_down"], d)
    return routed + _swiglu(h, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])


# ---------------------------------------------------------------------------
# The lanes
# ---------------------------------------------------------------------------

def sinkhorn(c, iters: int, eps: float):
    """c [.., n, n] -> the projected matrix."""
    m = jax.nn.softmax(c, axis=-1) + eps
    m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _dynamic(X, w, d):
    """m = (vec(X) w) r: [T, columns of w]."""
    xbar = X.reshape(X.shape[0], -1)
    r = jax.lax.rsqrt(jnp.mean(xbar * xbar, axis=-1, keepdims=True)
                      + d["eps"])
    return (xbar @ w) * r


def lane_mix(X, w, scale, base, d):
    """X [T, n, d] -> (pre [T, n], post [T, n], comb [T, n, n])."""
    n, m = d["lanes"], _dynamic(X, w, d)
    pre = jax.nn.sigmoid(scale[0] * m[:, :n] + base[:n]) + d["hc_eps"]
    post = 2.0 * jax.nn.sigmoid(scale[1] * m[:, n:2 * n] + base[n:2 * n])
    c = jnp.clip(scale[2] * m[:, 2 * n:] + base[2 * n:], d["clamp_min"],
                 d["clamp_max"]).reshape(-1, n, n)
    return pre, post, sinkhorn(c, d["hc_iters"], d["hc_eps"])


def _round(X, leaves, prefix, F, d):
    """A sublayer round the stream: X [T, n, d] (or [T, d] without lanes:
    x + F(x)); F takes the lanes' weighted sum, norm and all."""
    if not d["lanes"]:
        return X + F(X)
    pre, post, comb = lane_mix(X, leaves[prefix + "_w"],
                               leaves[prefix + "_scale"],
                               leaves[prefix + "_base"], d)
    y = F(jnp.einsum("ti,tid->td", pre, X))
    return post[:, :, None] * y[:, None, :] \
        + jnp.einsum("tji,tid->tjd", comb, X)


def _collapse(X, leaves, d):
    if not d["lanes"]:
        return X
    head = leaves["hc_head"]
    m = _dynamic(X, head["w"], d)
    pre = jax.nn.sigmoid(head["scale"][0] * m + head["base"]) + d["hc_eps"]
    return jnp.einsum("ti,tid->td", pre, X)


def _lanes_of(x, d):
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], d["lanes"],
                                            x.shape[1])) if d["lanes"] else x


# ---------------------------------------------------------------------------
# The pieces a pass is walked by, each one jitted program
# ---------------------------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


@partial(jax.jit, static_argnames=("which", "kind", "dims"))
def _sublayer(X, lp, *, which, kind, dims):
    """One sublayer (`which`: "attn" or "ffn") of a layer on one sequence."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        if which == "attn":
            return _round(X, lp, "hc_attn", lambda u: _attention(
                _rms_norm(u, lp["ln1_w"], d["eps"]), lp, d), d)
        return _round(X, lp, "hc_ffn", lambda u: _feed_forward(
            _rms_norm(u, lp["ln2_w"], d["eps"]), lp, kind, d), d)


@partial(jax.jit, static_argnames=("which", "kind", "dims"))
def _sublayer_back(X, lp, g, *, which, kind, dims):
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(partial(_sublayer, which=which, kind=kind,
                                  dims=dims), X, lp)
        return pull(g)


@partial(jax.jit, static_argnames=("dims",))
def _behind(X, leaves, *, dims):
    """The stream behind its last layer -> (x before the final norm, the
    final norm's output)."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        leaves = _f32(leaves)
        x = _collapse(X, leaves, d)
        return x, _rms_norm(x, leaves["final_norm_w"], d["eps"])


@partial(jax.jit, static_argnames=("dims",))
def _behind_back(X, leaves, g_x, g_final, *, dims):
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(partial(_behind, dims=dims), X, leaves)
        return pull((g_x, g_final))


@partial(jax.jit, static_argnames=("dims",))
def _mtp_in(x, e, leaves, *, dims):
    """h' = [RMSNorm(e) ; RMSNorm(x)] W_eh as lanes."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        leaves = _f32(leaves)
        both = jnp.concatenate(
            [_rms_norm(e, leaves["enorm_w"], d["eps"]),
             _rms_norm(x, leaves["hnorm_w"], d["eps"])], axis=-1)
        return _lanes_of(both @ leaves["w_eh"], d)


@partial(jax.jit, static_argnames=("dims",))
def _mtp_in_back(x, e, leaves, g, *, dims):
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(partial(_mtp_in, dims=dims), x, e, leaves)
        return pull(g)


def _pick(tree, names):
    return {k: tree[k] for k in names if k in tree}


def _leaves(prefix, tree):
    """(keys, leaf) of every leaf of a nest of dicts, `prefix` in front."""
    for name, value in tree.items():
        if isinstance(value, dict):
            yield from _leaves(prefix + (name,), value)
        else:
            yield prefix + (name,), value


_BEHIND = ("final_norm_w", "hc_head")
_MTP_IN = ("enorm_w", "hnorm_w", "w_eh")


def _loss_back(final, head, targets, weights, steps):
    """d sum(weights x nll) / steps wrt (final, head), a block of rows at a
    time."""
    g_final, g_head = [], jnp.zeros(head.shape, F32)
    for start in range(0, final.shape[0], LOGIT_ROWS):
        rows = slice(start, start + LOGIT_ROWS)
        gx, gh = jax.grad(
            lambda x, e, t, w: jnp.sum(_rows_nll(x, e, t) * w) / steps,
            (0, 1))(final[rows], head, targets[rows], weights[rows])
        g_final.append(gx)
        g_head = g_head + gh
    return jnp.concatenate(g_final), g_head


class Pass:
    """One sequence of T tokens through the layers, float32: `last` [T, d]
    (the collapsed stream before the final norm), `final` (the final
    RMSNorm's output) and, where `for_grads`, each sublayer's input kept for
    `grads()`."""

    def __init__(self, params: dict, tokens, dims: dict, for_grads=False):
        self.params, self.dims = params, dims
        self.static = tuple(sorted(dims.items()))
        self.tokens = tokens = jnp.asarray(tokens, jnp.int32)
        self.layers = list(_layers_in_order(params))
        if len(self.layers) != dims["layers"]:
            raise ValueError(f"{len(self.layers)} layers of parameters, "
                             f"num_hidden_layers {dims['layers']}")
        self.kinds = ["dense" if i < dims["dense_layers"] else "moe"
                      for i in range(dims["layers"])]
        self.for_grads = for_grads
        X = _lanes_of(params["tok_embed"][tokens].astype(F32), dims)
        self.inputs, self.probe_input = [], None
        for layer, where in enumerate(self.layers):
            X, kept = self._layer(X, _layer_params(params, where),
                                  self.kinds[layer])
            # on the HOST: ten float32 streams of 8192 tokens are 4.7 GB
            # and a sublayer's walk back does not load beside them and a
            # train state
            self.inputs.append(jax.device_get(kept) if for_grads else None)
            if self.kinds[layer] == "moe":
                self.probe_input = kept[1].astype(jnp.bfloat16)
        self.stream = X if for_grads else None
        self.last, self.final = _behind(X, _pick(params, _BEHIND),
                                        dims=self.static)
        self._mtp = None

    def _layer(self, X, lp, kind):
        """-> (the layer's output, (its input, its second sublayer's))."""
        mid = _sublayer(X, lp, which="attn", kind=kind, dims=self.static)
        return _sublayer(mid, lp, which="ffn", kind=kind,
                         dims=self.static), (X, mid)

    # -- the probe ----------------------------------------------------------
    def residual_mix(self):
        """The lanes' mixing ALONE, round the identity: (the operands as the
        program's `residual_mix` takes them: the LAST expert layer's stream
        in front of its feed-forward as bfloat16 [1, T, n d], the way the
        lanes cross HBM, and that sublayer's three lane leaves; X' [1, T, n
        d] float32 = post u + comb X, u = sum pre X, from those operands)."""
        d = self.dims
        last = max(i for i, k in enumerate(self.kinds) if k == "moe")
        lp = _layer_params(self.params, self.layers[last])
        leaves = (lp["hc_ffn_w"], lp["hc_ffn_scale"], lp["hc_ffn_base"])
        X = self.probe_input
        with jax.default_matmul_precision("highest"):
            out = _round(X.astype(F32), dict(zip(
                ("p_w", "p_scale", "p_base"), _f32(leaves))), "p",
                lambda u: u, d)
        T = X.shape[0]
        return (X.reshape(1, T, -1), *leaves), out.reshape(1, T, -1)

    # -- the second loss ----------------------------------------------------
    def _second(self, targets):
        """The block's forward for these targets: (its stream's inputs, its
        final norm's output)."""
        if self._mtp is None:
            params, mp = self.params, self.params["mtp"]
            e = params["tok_embed"][targets].astype(F32)
            X0 = _mtp_in(self.last, e, _pick(mp, _MTP_IN), dims=self.static)
            X, kept = self._layer(X0, mp["layer"], "moe")
            x, final = _behind(X, _pick(mp, _BEHIND), dims=self.static)
            self._mtp = (e, kept, X, final)
        return self._mtp

    def _blocks_nll(self, final, targets):
        return jnp.concatenate([
            _rows_nll(final[start:start + LOGIT_ROWS], self.params["lm_head"],
                      targets[start:start + LOGIT_ROWS])
            for start in range(0, final.shape[0], LOGIT_ROWS)])

    def _ahead(self, targets):
        """(the tokens two positions on, any id at the last position; 1
        where a position has one)."""
        T = targets.shape[0]
        return (jnp.concatenate([targets[1:], targets[:1]]),
                (jnp.arange(T) < T - 1).astype(F32))

    def mtp_nll(self, targets):
        """-log p_block(tokens[t+2]) at every position, 0 at the last."""
        targets = jnp.asarray(targets, jnp.int32)
        ahead, has = self._ahead(targets)
        return self._blocks_nll(self._second(targets)[3], ahead) * has

    def token_nll(self, targets):
        """A position's objective: -log p(targets[t] | tokens[:t+1]) plus,
        with the block, `mtp_weight` x its loss: [T]."""
        targets = jnp.asarray(targets, jnp.int32)
        nll = self._blocks_nll(self.final, targets)
        if self.dims["mtp"]:
            nll = nll + self.dims["mtp_weight"] * self.mtp_nll(targets)
        return nll

    # -- the walk back --------------------------------------------------------
    def _layer_back(self, kept, lp, kind, g):
        X, mid = kept
        g, g_ffn = _sublayer_back(mid, lp, g, which="ffn", kind=kind,
                                  dims=self.static)
        g, g_attn = _sublayer_back(X, lp, g, which="attn", kind=kind,
                                   dims=self.static)
        return g, jax.tree.map(jnp.add, g_ffn, g_attn)

    def grads(self, targets):
        """The gradient of mean(token_nll(targets)), walked back a sublayer
        at a time: yields (keys into the program's parameters, gradient):
        the top leaves each alone (a group's as (group, name)), the block's
        as (("mtp", .., name), gradient), the layers from the last to the first as
        (("layers", segment, position, repeat), {name: gradient}), the
        embedding last."""
        params, d = self.params, self.dims
        targets = jnp.asarray(targets, jnp.int32)
        T, head = self.final.shape[0], params["lm_head"]
        g_embed = jnp.zeros(params["tok_embed"].shape, F32)
        g_final, g_head = _loss_back(self.final, head, targets,
                                     jnp.ones((T,), F32), T)
        g_last = jnp.zeros_like(self.last)
        if d["mtp"]:
            mp = params["mtp"]
            e, kept, X, final = self._second(targets)
            ahead, has = self._ahead(targets)
            g_f, g_h = _loss_back(final, head, ahead,
                                  has * d["mtp_weight"], T)
            g_head = g_head + g_h
            g, g_behind = _behind_back(X, _pick(mp, _BEHIND),
                                       jnp.zeros_like(self.last), g_f,
                                       dims=self.static)
            g, g_layer = self._layer_back(kept, mp["layer"], "moe", g)
            g_last, g_e, g_in = _mtp_in_back(
                self.last, e, _pick(mp, _MTP_IN), g, dims=self.static)
            g_embed = g_embed.at[targets].add(g_e)
            yield from _leaves(("mtp",), {**g_behind, **g_in,
                                          "layer": g_layer})
        yield ("lm_head",), g_head
        g, g_behind = _behind_back(self.stream, _pick(params, _BEHIND),
                                   g_last, g_final, dims=self.static)
        yield from _leaves((), g_behind)
        for layer in reversed(range(len(self.layers))):
            g, g_lp = self._layer_back(
                self.inputs[layer], _layer_params(params, self.layers[layer]),
                self.kinds[layer], g)
            yield ("layers",) + self.layers[layer], g_lp
        if d["lanes"]:
            g = g.sum(axis=1)
        yield ("tok_embed",), g_embed.at[self.tokens].add(g)


def token_nll(params: dict, tokens, dims: dict):
    """A position's objective at every position of one sequence of S+1
    tokens: [S] float32."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return Pass(params, tokens[:-1], dims).token_nll(tokens[1:])


def batch_token_nll(params: dict, batch_tokens, dims: dict):
    """`token_nll` of every row of a batch [B, S+1]: [B, S] float32."""
    return jnp.stack([token_nll(params, row, dims) for row in batch_tokens])


def ffn_round(X, lp, d, experts_held, parts=("lanes", "shared", "routed")):
    """An expert layer's feed-forward sublayer round the stream for ANY
    share of the experts, the sum of the named parts of X'_j = sum_i comb_ji
    X_i ("lanes") + post_j Shared(h) + post_j (the share's experts' sum):
    what the shares-add-up test sums over the shares, the lanes' and the
    shared expert's terms counted once, and holds against the uncut layer
    (experts_held = (0, router_width), every part)."""
    first, held = experts_held
    d = {**d, "first_held": first, "held": held}
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        pre, post, comb = lane_mix(X, lp["hc_ffn_w"], lp["hc_ffn_scale"],
                                   lp["hc_ffn_base"], d)
        h = _rms_norm(jnp.einsum("ti,tid->td", pre, X), lp["ln2_w"], d["eps"])
        y = jnp.zeros_like(h)
        if "shared" in parts:
            y = y + _swiglu(h, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
        if "routed" in parts:
            y = y + _held_experts_sum(
                h, lp["router_w"], lp["router_bias"], lp["experts_gate"],
                lp["experts_up"], lp["experts_down"], d)
        out = post[:, :, None] * y[:, None, :]
        if "lanes" in parts:
            out = out + jnp.einsum("tji,tid->tjd", comb, X)
        return out
