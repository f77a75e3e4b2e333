"""Plain reference of the latent-attention, routed-expert decoder in the
published DeepSeek-V3 form (Kanana-2-30B-A3B: `model_type: deepseek_v3`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, nothing imported from
the program's `models/` or `ops/`.  Attention is a masked softmax over the
whole key axis, a block of queries and a group of heads at a time so that a
sequence of 8192 fits beside a train state.  The routed experts are computed
an expert at a time on every token (below).  `Pass.grads` is the
same forward walked back one layer at a time (each layer's `jax.vjp`).  It
reads the program's parameter LAYOUT (`params["layers"][segment]["0"][name]
[repeat]`, matrices `[in, out]`) so that it can be handed the program's own
weights.

The equations (h a layer's input, T tokens; no bias anywhere):

  block      x = x + Attn(RMSNorm(x)); x = x + FFN(RMSNorm(x)); a final
             RMSNorm; logits = h W_head^T (untied).
  attention  q = h W_q -> [T, heads, nope + rope]; c = h W_kva;
             c_kv = RMSNorm(c[:rank]) with a weight; k_pe = c[rank:], one
             head shared by all; c_kv W_kvb -> [T, heads, nope + v] =
             [k_nope | v]; rope (interleaved pairs (2i, 2i + 1), angle
             position x theta^(-i / (rope / 2))) on q_pe and k_pe;
             softmax(q k^T / sqrt(nope + rope)) v, causal; W_o.
  dense FFN  (silu(h Wg) * (h Wu)) Wd.
  expert FFN s = sigmoid(h W_r) [T, router_width]; sel = top k of s + b;
             g = s[sel] / sum(s[sel]) x routed_scaling_factor;
             y = sum over the HELD experts e in sel of g_e (silu(h Wg_e) *
             (h Wu_e)) Wd_e + Shared(h).

The share.  The parameters hold `held` experts, experts `first_held` on, of
the `router_width` the router scores.  The router and the top k run over all
of them; only the held experts' terms are summed (and the shared expert);
what the other experts would add is left out, here as in the program.

An expert's tokens.  Every held expert is computed on EVERY token and
weighted by the token's gate for it, which is exactly zero where the token
did not choose it: no sort, no capacity, nothing that could drop a row.
Sixteen full-width float32 feed-forwards a layer, one after another.

Departures from the published description: none known; what could not be
confirmed offline is under `assumed` in the configuration.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # queries a block of scores holds
HEAD_GROUP = 8          # heads a block of scores holds
LOGIT_ROWS = 1024       # rows of logits the loss holds at a time


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group (the published key names + the chip's share)."""
    held = int(model["n_routed_experts"])
    return {
        "heads": int(model["num_attention_heads"]),
        "nope": int(model["qk_nope_head_dim"]),
        "rope": int(model["qk_rope_head_dim"]),
        "v": int(model["v_head_dim"]),
        "rank": int(model["kv_lora_rank"]),
        "eps": float(model["rms_norm_eps"]),
        "theta": float(model["rope_theta"]),
        "dense_layers": int(model["first_k_dense_replace"]),
        "layers": int(model["num_hidden_layers"]),
        "top_k": int(model["num_experts_per_tok"]),
        "scale": float(model["routed_scaling_factor"]),
        "held": held,
        "first_held": int(model.get("first_held_expert", 0)),
        "router_width": int(model.get("router_width") or held),
    }


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, heads, rope]: pairs (2i, 2i + 1) turned by position x
    theta^(-i / (rope / 2))."""
    T, _, width = x.shape
    half = width // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(T, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _causal_attention(q, k, v):
    """q, k [T, heads, d], v [T, heads, e] -> [T, heads, e]; query t sees
    keys 0 .. t."""
    T, heads, d = q.shape
    block, group = min(QUERY_BLOCK, T), min(HEAD_GROUP, heads)
    pad = -T % block
    # [head groups, heads of one, T, .]
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2).reshape(
        heads // group, group, T + pad, d)
    kg = k.transpose(1, 0, 2).reshape(heads // group, group, T, d)
    vg = v.transpose(1, 0, 2).reshape(heads // group, group, T, -1)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def one_block(start):
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]

        @jax.checkpoint
        def one_group(args):
            qj, kj, vj = args
            scores = jnp.einsum("hqd,hkd->hqk", qj, kj) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,hke->hqe", probs, vj)

        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=2)
        return jax.lax.map(one_group, (qb, kg, vg))

    a = jax.lax.map(one_block, jnp.arange(0, T + pad, block))
    # [blocks, groups, group, block, e] -> [T, heads, e]
    return a.transpose(0, 3, 1, 2, 4).reshape(T + pad, heads, -1)[:T]


def _attention(u, lp, d):
    T = u.shape[0]
    heads, nope, rope, rank = d["heads"], d["nope"], d["rope"], d["rank"]
    q = (u @ lp["wq"]).reshape(T, heads, nope + rope)
    latent = u @ lp["wkv_a"]
    c_kv = _rms_norm(latent[:, :rank], lp["kv_norm_w"], d["eps"])
    kv = (c_kv @ lp["wkv_b"]).reshape(T, heads, nope + d["v"])
    k_pe = _rope(latent[:, None, rank:], d["theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], d["theta"])],
                        axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (T, heads, rope))], axis=-1)
    a = _causal_attention(q, k, kv[..., nope:])
    return a.reshape(T, heads * d["v"]) @ lp["wo"]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _select(h, router_w, router_bias, d):
    """-> (sel [T, k]: the experts of every token, over all the router's;
    gates [T, k])."""
    scores = jax.nn.sigmoid(h @ router_w)
    _, sel = jax.lax.top_k(scores + router_bias, d["top_k"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, picked / jnp.sum(picked, axis=-1, keepdims=True) * d["scale"]


def _held_experts_sum(h, router_w, router_bias, w_gate, w_up, w_down, d):
    """The held experts' terms summed: [T, hidden]."""
    sel, gates = _select(h, router_w, router_bias, d)
    held = d["first_held"] + jnp.arange(d["held"])
    # [T, held]: a token's gate for each held expert, 0 where not chosen
    gate_of = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                gates[:, :, None], 0.0), axis=1)

    def add_expert(y, expert):
        w_g, w_u, w_d, gate = expert
        return y + _swiglu(h, w_g, w_u, w_d) * gate[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (w_gate, w_up, w_down, gate_of.T))
    return y


@partial(jax.jit, static_argnames=("kind", "dims"))
def _layer(x, lp, *, kind, dims):
    """One layer on one sequence, x [T, hidden] float32 -> x."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        x = x + _attention(_rms_norm(x, lp["ln1_w"], d["eps"]), lp, d)
        h = _rms_norm(x, lp["ln2_w"], d["eps"])
        if kind == "dense":
            return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        routed = _held_experts_sum(
            h, lp["router_w"], lp["router_bias"], lp["experts_gate"],
            lp["experts_up"], lp["experts_down"], d)
        shared = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])
        return x + routed + shared


@partial(jax.jit, static_argnames=("dims",))
def _routed_alone(x, lp, *, dims):
    """An expert layer's normed FFN input and its held experts' sum alone,
    from the layer's input."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        x = x + _attention(_rms_norm(x, lp["ln1_w"], d["eps"]), lp, d)
        h = _rms_norm(x, lp["ln2_w"], d["eps"])
        return h, _held_experts_sum(
            h, lp["router_w"], lp["router_bias"], lp["experts_gate"],
            lp["experts_up"], lp["experts_down"], d)


@partial(jax.jit, static_argnames=("kind", "dims"))
def _layer_back(x, lp, g_x, *, kind, dims):
    """The cotangents of a layer's (x, lp) from that of its output x: the
    layer computed again, then walked back."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(
            partial(_layer, kind=kind, dims=dims), x, lp)
        return pull(g_x)


@jax.jit
def _head(x, lm_head):
    with jax.default_matmul_precision("highest"):
        return x @ lm_head.astype(F32).T


def _rows_nll(x, lm_head, targets):
    logp = jax.nn.log_softmax(_head(x, lm_head), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def _layers_in_order(params: dict):
    """The layers in the model's order, as (segment, position, repeat)."""
    for seg_name in sorted(params["layers"]):
        seg = params["layers"][seg_name]
        for pos in sorted(seg, key=int):
            repeats = jax.tree.leaves(seg[pos])[0].shape[0]
            for rep in range(repeats):
                yield seg_name, pos, rep


def _layer_params(params: dict, where):
    seg_name, pos, rep = where
    return jax.tree.map(lambda a: a[rep], params["layers"][seg_name][pos])


class Pass:
    """One sequence of T tokens through the layers, float32: `final` [T,
    hidden] (the final RMSNorm's output), the last expert layer's
    `routed_experts()` alone and, where `for_grads`, each layer's input kept
    for `grads()`."""

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
        x = params["tok_embed"][tokens].astype(F32)
        self.inputs = []
        self.last_expert_input = None
        for layer, where in enumerate(self.layers):
            if for_grads:
                self.inputs.append(x)
            if self.kinds[layer] == "moe":
                self.last_expert_input = x
            x = _layer(x, _layer_params(params, where),
                       kind=self.kinds[layer], dims=self.static)
        self.last = x
        self.final = _rms_norm(x, params["final_norm_w"].astype(F32),
                               dims["eps"])

    def routed_experts(self):
        """The LAST expert layer's routed part alone: (its operands as the
        program's `routed_experts` takes them: the normed input with a
        batch axis of one, the router's weight and selection bias, the held
        experts' three weights; the held experts' sum [1, T, hidden])."""
        last = max(i for i, k in enumerate(self.kinds) if k == "moe")
        lp = _layer_params(self.params, self.layers[last])
        h, routed = _routed_alone(self.last_expert_input, lp,
                                  dims=self.static)
        return ((h[None], lp["router_w"], lp["router_bias"],
                 lp["experts_gate"], lp["experts_up"], lp["experts_down"]),
                routed[None])

    def token_nll(self, targets):
        """-log p(targets[t] | tokens[:t+1]) at every position: [T]; the
        logits a block of rows at a time."""
        targets = jnp.asarray(targets, jnp.int32)
        return jnp.concatenate([
            _rows_nll(self.final[start:start + LOGIT_ROWS],
                      self.params["lm_head"],
                      targets[start:start + LOGIT_ROWS])
            for start in range(0, self.final.shape[0], LOGIT_ROWS)])

    def grads(self, targets):
        """The gradient of mean(token_nll(targets)), walked back one layer
        at a time: yields (keys into the program's parameters, gradient),
        the head and the final RMSNorm first, then the layers from the last
        to the first as (("layers", segment, position, repeat), {name:
        gradient}), the embedding last."""
        params, dims, tokens = self.params, self.dims, self.tokens
        targets = jnp.asarray(targets, jnp.int32)
        head = params["lm_head"]
        steps = self.final.shape[0]
        g_final, g_head = [], jnp.zeros(head.shape, F32)
        for start in range(0, steps, LOGIT_ROWS):
            gx, gh = jax.grad(
                lambda x, e, t: jnp.sum(_rows_nll(x, e, t)) / steps, (0, 1))(
                self.final[start:start + LOGIT_ROWS], head,
                targets[start:start + LOGIT_ROWS])
            g_final.append(gx)
            g_head = g_head + gh
        yield ("lm_head",), g_head
        _, pull = jax.vjp(lambda x, w: _rms_norm(x, w, dims["eps"]),
                          self.last, params["final_norm_w"].astype(F32))
        gx, gw = pull(jnp.concatenate(g_final))
        yield ("final_norm_w",), gw
        for layer in reversed(range(len(self.layers))):
            gx, g_lp = _layer_back(
                self.inputs[layer],
                _layer_params(params, self.layers[layer]), gx,
                kind=self.kinds[layer], dims=self.static)
            yield ("layers",) + self.layers[layer], g_lp
        yield ("tok_embed",), jnp.zeros(
            params["tok_embed"].shape, F32).at[tokens].add(gx)


def token_nll(params: dict, tokens, dims: dict):
    """-log p(tokens[t+1] | tokens[:t+1]) at every position of one
    sequence of S+1 tokens: [S] float32."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return Pass(params, tokens[:-1], dims).token_nll(tokens[1:])


def batch_token_nll(params: dict, batch_tokens, dims: dict):
    """`token_nll` of every row of a batch [B, S+1], one sequence at a
    time: [B, S] float32."""
    return jnp.stack([token_nll(params, row, dims) for row in batch_tokens])


def whole_layer_ffn(h, lp, d, experts_held):
    """An expert layer's routed sum for ANY share of the experts, on its
    normed input: what the shares-add-up test sums over the shares and
    holds against the uncut layer (experts_held = (0, router_width))."""
    first, held = experts_held
    with jax.default_matmul_precision("highest"):
        return _held_experts_sum(
            h, lp["router_w"], lp["router_bias"], lp["experts_gate"],
            lp["experts_up"], lp["experts_down"],
            {**d, "first_held": first, "held": held})
