"""Plain reference of the windowed / full grouped-query decoder with per-head
output gates and softmax-routed experts in the published `laguna` form
(Laguna-S-2.1: `model_type: laguna`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, nothing imported from
the program's `models/` or `ops/`.  Attention is a masked softmax over the
whole key axis, a block of queries and one KV head's group of query heads at
a time, so that a sequence of 8192 fits beside a train state.  The routed
experts are computed an expert at a time on every token (below).
`Pass.grads` is the same forward walked back one layer at a time (each
layer's `jax.vjp`).  It reads the program's parameter LAYOUT
(`params["layers"][segment]["0"][name][repeat]`, matrices `[in, out]`) so
that it can be handed the program's own weights.

The equations.  For layer l with input x [s, hidden], H_l query heads
(`num_attention_heads_per_layer`) over KV = `num_key_value_heads` heads of
d = `head_dim`, group g_l = H_l / KV; no bias anywhere:

  1. u = RMSNorm(x).  q = u W_q [s, H_l, d], k = u W_k [s, KV, d], v = u
     W_v [s, KV, d].
  2. Rope, by the layer's kind (`rope_parameters`).  `sliding_attention`:
     all d dimensions, angle t x theta^(-2i/d) (theta 10,000), half-split
     pairing (i, i + d/2), the Hugging Face default.  `full_attention`: the
     FIRST r = d x `partial_rotary_factor` dimensions only, pairing (i, i +
     r/2), inverse frequencies by yarn: with base theta (500,000),
     extrap_i = base^(-2i/r), interp_i = extrap_i / factor; low, high = the
     correction range for `beta_fast` and `beta_slow` rotations at
     `original_max_position_embeddings` (floor, ceil and clamp as Hugging
     Face's `_compute_yarn_parameters` has them for a rotary width of r);
     ramp_i = clip((i - low) / (high - low), 0, 1); inv_freq_i = interp_i x
     ramp_i + extrap_i x (1 - ramp_i); cos and sin both multiplied by
     `attention_factor`.  The other d - r dimensions pass through.
  3. Attention.  Query head j reads KV head j // g_l.  Scores q k^T /
     sqrt(d), causal; in a sliding layer query t sees keys s with 0 <= t -
     s < `sliding_window`.  Softmax in float32.  a_j = softmax x v [s, d].
  4. Gate: G = sigmoid(u W_g) [s, H_l], W_g [hidden, H_l]; a_j <- G[:, j] x
     a_j.
  5. x <- x + concat_j(a_j) W_o.
  6. y = RMSNorm(x).  A layer in `mlp_only_layers`: x <- x + SwiGLU(y) of
     `intermediate_size`.  Every other: p = softmax(y W_r) over
     `router_width` in float32; the token's experts are the top
     `num_experts_per_tok` of p; gates p[sel] / sum(p[sel]) x
     `moe_routed_scaling_factor`; x <- x + sum over the chosen experts HELD
     HERE of gate_e x SwiGLU_e(y) + SwiGLU_shared(y).
  7. Final RMSNorm, untied head, mean next-token cross-entropy.

The share.  The parameters hold `held` experts, experts `first_held` on, of
the `router_width` the router scores.  The router and the top k run over all
of them; only the held experts' terms are summed (and the shared expert);
what the other experts would add is left out, here as in the program.

An expert's tokens.  Every held expert is computed on EVERY token and
weighted by the token's gate for it, which is exactly zero where the token
did not choose it: no sort, no capacity, nothing that could drop a row.

Assumptions (each with its reason under `assumed` in the configuration):
the softmax router without a selection bias, the gate's form (step 4), the
ungated shared expert, no q / k norm and no attention sink, no auxiliary
loss, the window read as step 3 has it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # queries a block of scores holds
LOGIT_ROWS = 1024       # rows of logits the loss holds at a time
FULL, SLIDING = "full_attention", "sliding_attention"


def _pairs(x):
    return tuple(sorted(x.items()))


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group (the published key names + the chip's share).  The per-layer
    lists may be the published whole ones: the first `num_hidden_layers`
    entries are this program's."""
    n = int(model["num_hidden_layers"])
    held = int(model["num_experts"])
    heads = model.get("num_attention_heads_per_layer") \
        or [model["num_attention_heads"]] * n
    return {
        "layers": n,
        "kinds": tuple(model["layer_types"][:n]),
        "heads": tuple(int(h) for h in heads[:n]),
        "kv": int(model["num_key_value_heads"]),
        "d": int(model["head_dim"]),
        "window": int(model["sliding_window"]),
        "rope": tuple(sorted((kind, _pairs(r)) for kind, r in
                             model["rope_parameters"].items())),
        "dense_layers": tuple(int(i) for i in model["mlp_only_layers"]),
        "eps": float(model["rms_norm_eps"]),
        "top_k": int(model["num_experts_per_tok"]),
        "scale": float(model["moe_routed_scaling_factor"]),
        "held": held,
        "first_held": int(model.get("first_held_expert", 0)),
        "router_width": int(model.get("router_width") or held),
    }


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(rope: dict, r: int):
    """Step 2's yarn frequencies [r / 2] for a rotary width of r."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (r * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(r // 2, dtype=F32)
    extrap = base ** (-2.0 * i / r)
    interp = extrap / factor
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp)


def _rope(x, rope: dict):
    """x [T, heads, d]: its first r = d x partial_rotary_factor dimensions
    turned, pair (i, i + r/2), the others as they are."""
    T, _, d = x.shape
    r = int(d * rope.get("partial_rotary_factor", 1))
    if rope.get("rope_type", "default") == "yarn":
        inv_freq = yarn_inv_freq(rope, r)
    else:
        inv_freq = float(rope["rope_theta"]) ** (
            -2.0 * jnp.arange(r // 2, dtype=F32) / r)
    angle = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    factor = float(rope.get("attention_factor", 1.0))
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           axis=-1)


def _grouped_attention(q, k, v, window):
    """q [T, H, d], k, v [T, KV, d] -> [T, H, d]; query head j reads KV head
    j // (H / KV); query t sees keys s <= t, and with a window only those
    with t - s < window."""
    T, heads, d = q.shape
    kv = k.shape[1]
    group, block = heads // kv, min(QUERY_BLOCK, T)
    pad = -T % block
    # [KV heads, the group's query heads, T, d]
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2).reshape(
        kv, group, T + pad, d)
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def one_block(start):
        behind = (start + jnp.arange(block))[:, None] - key_pos[None, :]
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)

        @jax.checkpoint
        def one_kv_head(args):
            qj, kj, vj = args
            scores = jnp.einsum("gqd,kd->gqk", qj, kj) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->gqd", probs, vj)

        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=2)
        return jax.lax.map(one_kv_head, (qb, kg, vg))

    a = jax.lax.map(one_block, jnp.arange(0, T + pad, block))
    # [blocks, KV, group, block, d] -> [T, H, d]
    return a.transpose(0, 3, 1, 2, 4).reshape(T + pad, heads, d)[:T]


def _attention(u, lp, kind, heads, d):
    T = u.shape[0]
    kv, hd = d["kv"], d["d"]
    rope = dict(dict(d["rope"])[kind])
    q = _rope((u @ lp["wq"]).reshape(T, heads, hd), rope)
    k = _rope((u @ lp["wk"]).reshape(T, kv, hd), rope)
    v = (u @ lp["wv"]).reshape(T, kv, hd)
    a = _grouped_attention(q, k, v, d["window"] if kind == SLIDING else None)
    gate = jax.nn.sigmoid(u @ lp["wg"])                 # [T, H]
    return (a * gate[:, :, None]).reshape(T, heads * hd) @ lp["wo"]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _select(h, router_w, d):
    """-> (sel [T, k]: the experts of every token, over all the router's;
    gates [T, k])."""
    probs = jax.nn.softmax(h @ router_w, axis=-1)
    picked, sel = jax.lax.top_k(probs, d["top_k"])
    return sel, picked / jnp.sum(picked, axis=-1, keepdims=True) * d["scale"]


def _held_experts_sum(h, router_w, w_gate, w_up, w_down, d):
    """The held experts' terms summed: [T, hidden]."""
    sel, gates = _select(h, router_w, d)
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


def _attended(x, lp, layer, d):
    """-> (x after attention's residual, the FFN's normed input)."""
    u = _rms_norm(x, lp["ln1_w"], d["eps"])
    x = x + _attention(u, lp, d["kinds"][layer], d["heads"][layer], d)
    return x, _rms_norm(x, lp["ln2_w"], d["eps"])


@partial(jax.jit, static_argnames=("layer", "dims"))
def _layer(x, lp, *, layer, dims):
    """Layer `layer` on one sequence, x [T, hidden] float32 -> x."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        x, h = _attended(x, lp, layer, d)
        if layer in d["dense_layers"]:
            return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        routed = _held_experts_sum(
            h, lp["router_w"], lp["experts_gate"], lp["experts_up"],
            lp["experts_down"], d)
        shared = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])
        return x + routed + shared


@partial(jax.jit, static_argnames=("layer", "dims"))
def _routed_alone(x, lp, *, layer, dims):
    """An expert layer's normed FFN input and its held experts' sum alone,
    from the layer's input."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        _, h = _attended(x, lp, layer, d)
        return h, _held_experts_sum(
            h, lp["router_w"], lp["experts_gate"], lp["experts_up"],
            lp["experts_down"], d)


@partial(jax.jit, static_argnames=("layer", "dims"))
def _layer_back(x, lp, g_x, *, layer, dims):
    """The cotangents of a layer's (x, lp) from that of its output x: the
    layer computed again, then walked back."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(partial(_layer, layer=layer, dims=dims), x, lp)
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
        self.static = _pairs(dims)
        self.tokens = tokens = jnp.asarray(tokens, jnp.int32)
        self.layers = list(_layers_in_order(params))
        if len(self.layers) != dims["layers"]:
            raise ValueError(f"{len(self.layers)} layers of parameters, "
                             f"num_hidden_layers {dims['layers']}")
        experts = [i for i in range(dims["layers"])
                   if i not in dims["dense_layers"]]
        self.last_expert_layer = experts[-1] if experts else None
        x = params["tok_embed"][tokens].astype(F32)
        self.inputs = []
        self.last_expert_input = None
        for layer, where in enumerate(self.layers):
            if for_grads:
                self.inputs.append(x)
            if layer == self.last_expert_layer:
                self.last_expert_input = x
            x = _layer(x, _layer_params(params, where), layer=layer,
                       dims=self.static)
        self.last = x
        self.final = _rms_norm(x, params["final_norm_w"].astype(F32),
                               dims["eps"])

    def routed_experts(self):
        """The LAST expert layer's routed part alone: (its operands as the
        program's `routed_experts` takes them: the normed input with a
        batch axis of one, the router's weight, the held experts' three
        weights; the held experts' sum [1, T, hidden])."""
        last = self.last_expert_layer
        lp = _layer_params(self.params, self.layers[last])
        h, routed = _routed_alone(self.last_expert_input, lp, layer=last,
                                  dims=self.static)
        return ((h[None], lp["router_w"], lp["experts_gate"],
                 lp["experts_up"], lp["experts_down"]), routed[None])

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
                layer=layer, dims=self.static)
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
            h, lp["router_w"], lp["experts_gate"], lp["experts_up"],
            lp["experts_down"], {**d, "first_held": first, "held": held})
