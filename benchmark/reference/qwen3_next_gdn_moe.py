"""Plain reference of the gated-delta-rule / gated-full-attention decoder with
softmax-routed experts and a gated shared expert in the published
`qwen3_next` form (Qwen3-Next-80B-A3B: `model_type: qwen3_next`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, nothing imported from
the program's `models/` or `ops/`.  The linear layers' recurrence is walked
ONE STEP AT A TIME (`lax.scan` over time; the steps are grouped by 64 under
`jax.checkpoint` only so that a backward holds one state a group and not one
a step: nothing of the chunked algebra is here).  Full attention is a masked
softmax over the whole key axis, a block of queries and one KV head's group
of query heads at a time.  The routed experts are computed an expert at a
time on every token.  `Pass.grads` is the same forward walked back one layer
at a time (each layer's `jax.vjp`).  It reads the program's parameter LAYOUT
(`params["layers"][segment]["0"][name][repeat]`, matrices `[in, out]`) so
that it can be handed the program's own weights.

The equations.  Z(x; w) = x / sqrt(mean(x^2) + eps) (1 + w), the family's
zero-centred RMSNorm (eps `rms_norm_eps`); no bias anywhere.  Layer i is
full attention where (i + 1) % `full_attention_interval` == 0, else linear.
x [s, hidden]:

  linear  1. u = Z(x; ln1_w).  [q | k | v | z] = u W_qkvz, widths H_k d_k,
             H_k d_k, H_v d_v, H_v d_v; [b | a] = u W_ba, H_v each.
          2. [q | k | v] <- silu(conv([q | k | v])): y_t = sum_j w[j]
             x_{t - 3 + j} over `linear_conv_kernel_dim` = 4 taps, causal,
             a channel at a time, no bias.
          3. beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias), one
             a value head.
          4. q, k by key head [s, H_k, d_k], each repeated H_v / H_k times
             to the value heads (`repeat_interleave`); q <- q / sqrt(sum q^2
             + 1e-6) / sqrt(d_k), k <- k / sqrt(sum k^2 + 1e-6).
          5. a head, S [d_k, d_v] from zero, t = 0 ..: S <- exp(g_t) S;
             d_t = beta_t (v_t - S^T k_t); S <- S + k_t d_t^T; o_t = S^T
             q_t.
          6. y = o / sqrt(mean(o^2) + eps) w_n silu(z) a head (w_n [d_v], a
             plain weight); x <- x + concat(y) W_o.
  full    1. u = Z(x; ln1_w).  [q | gate] = u W_q a head (`head_dim` query
             columns, then `head_dim` gate columns); k = u W_k, v = u W_v
             over `num_key_value_heads`.
          2. q <- Z(q; q_norm_w), k <- Z(k; k_norm_w) over a head's columns.
          3. rope on the FIRST r = head_dim x `partial_rotary_factor`
             columns: angle t x theta^(-2i/r), pairing (i, i + r/2); the
             others pass through.
          4. query head j reads KV head j // (H / KV); scores q k^T /
             sqrt(head_dim), causal; softmax; a_j = softmax x v.
          5. x <- x + (concat(a_j) x sigmoid(gate)) W_o: a gate an ELEMENT.
  FFN     y = Z(x; ln2_w).  p = softmax(y W_r) over `router_width`; the top
          `num_experts_per_tok` of p; gates p[sel] / sum(p[sel]); x <- x +
          sum over the chosen experts HELD HERE of gate_e SwiGLU_e(y) +
          sigmoid(y w_sg) SwiGLU_shared(y).
  end     Z(x; final_norm_w), untied head, mean next-token cross-entropy.

The share.  The parameters hold `held` experts, experts `first_held` on, of
the `router_width` the router scores.  The router and the top k run over all
of them; only the held experts' terms are summed (and the shared expert);
what the other experts would add is left out, here as in the program.

Assumptions (each with its reason under `assumed` in the configuration):
the zero-centred norm and its plain gated sibling, the conv without bias,
the l2norm and the query's scale, the state from zero, the forms of
softplus and A_log, no multi-token-prediction module, no auxiliary loss.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # queries a block of scores holds
LOGIT_ROWS = 1024       # rows of logits the loss holds at a time
STEPS_A_GROUP = 64      # steps of the recurrence under one checkpoint
FULL, LINEAR = "full_attention", "linear_attention"
L2_EPS = 1e-6


def _pairs(x):
    return tuple(sorted(x.items()))


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group (the published key names + the chip's share)."""
    n = int(model["num_hidden_layers"])
    every = int(model["full_attention_interval"])
    held = int(model["num_experts"])
    return {
        "layers": n,
        "kinds": tuple(FULL if (i + 1) % every == 0 else LINEAR
                       for i in range(n)),
        "heads": int(model["num_attention_heads"]),
        "kv": int(model["num_key_value_heads"]),
        "d": int(model["head_dim"]),
        "rotary": int(int(model["head_dim"])
                      * float(model["partial_rotary_factor"])),
        "theta": float(model["rope_theta"]),
        "hk": int(model["linear_num_key_heads"]),
        "hv": int(model["linear_num_value_heads"]),
        "dk": int(model["linear_key_head_dim"]),
        "dv": int(model["linear_value_head_dim"]),
        "taps": int(model["linear_conv_kernel_dim"]),
        "eps": float(model["rms_norm_eps"]),
        "top_k": int(model["num_experts_per_tok"]),
        "held": held,
        "first_held": int(model.get("first_held_expert", 0)),
        "router_width": int(model.get("router_width") or held),
    }


def _norm(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _zero_centred_norm(x, w, eps):
    return _norm(x, eps) * (1.0 + w)


# -- the linear layer --------------------------------------------------------

def _causal_conv(x, w):
    """x [T, channels], w [taps, channels]: y_t = sum_j w[j] x_{t - (taps -
    1) + j}, rows before 0 zero."""
    taps, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + T] * w[j] for j in range(taps))


def _recurrence(q, k, v, g, beta):
    """Step 5, every value head: q, k [T, H, d_k], v [T, H, d_v], g, beta
    [T, H] -> o [T, H, d_v]."""
    T, heads, dk = k.shape

    def step(S, inp):                   # S [H, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = inp
        S = jnp.exp(g_t)[:, None, None] * S
        d_t = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def group(S, inp):
        return jax.lax.scan(step, S, inp)

    pad = -T % STEPS_A_GROUP
    grouped = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (T + pad) // STEPS_A_GROUP, STEPS_A_GROUP, *a.shape[1:])
        for a in (q, k, v, g, beta))    # a padded step: beta 0, g 0
    _, o = jax.lax.scan(group, jnp.zeros((heads, dk, v.shape[-1]), F32),
                        grouped)
    return o.reshape(T + pad, heads, -1)[:T]


def _rule_operands(u, lp, d):
    """Steps 1-4 of a linear layer: (q, k by KEY head [T, H_k, d_k],
    normalised; v [T, H_v, d_v]; g, beta [T, H_v]; z [T, H_v, d_v])."""
    T = u.shape[0]
    hk, hv, dk, dv = d["hk"], d["hv"], d["dk"], d["dv"]
    wide = 2 * hk * dk + hv * dv
    qkvz = u @ lp["w_qkvz"]
    ba = u @ lp["w_ba"]
    qkv = jax.nn.silu(_causal_conv(qkvz[:, :wide], lp["conv_w"]))
    z = qkvz[:, wide:].reshape(T, hv, dv)
    q = qkv[:, :hk * dk].reshape(T, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(T, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(T, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
        / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, hv:] + lp["dt_bias"])
    return q, k, v, g, beta, z


def _rule_output(q, k, v, g, beta, d):
    group = d["hv"] // d["hk"]
    return _recurrence(jnp.repeat(q, group, axis=1),
                       jnp.repeat(k, group, axis=1), v, g, beta)


def _linear_mixer(u, lp, d):
    q, k, v, g, beta, z = _rule_operands(u, lp, d)
    o = _rule_output(q, k, v, g, beta, d)
    y = _norm(o, d["eps"]) * lp["gn_w"] * jax.nn.silu(z)
    return y.reshape(u.shape[0], -1) @ lp["wo"]


# -- the full layer ----------------------------------------------------------

def _rope(x, r: int, theta: float):
    """x [T, heads, d]: its first r columns turned, pair (i, i + r/2), the
    others as they are."""
    T = x.shape[0]
    inv_freq = theta ** (-2.0 * jnp.arange(r // 2, dtype=F32) / r)
    angle = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           axis=-1)


def _grouped_attention(q, k, v):
    """q [T, H, d], k, v [T, KV, d] -> [T, H, d]; query head j reads KV head
    j // (H / KV); query t sees keys s <= t."""
    T, heads, d = q.shape
    kv = k.shape[1]
    group, block = heads // kv, min(QUERY_BLOCK, T)
    pad = -T % block
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2).reshape(
        kv, group, T + pad, d)
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def one_block(start):
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]

        @jax.checkpoint
        def one_kv_head(args):
            qj, kj, vj = args
            scores = jnp.einsum("gqd,kd->gqk", qj, kj) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->gqd", probs, vj)

        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=2)
        return jax.lax.map(one_kv_head, (qb, kg, vg))

    a = jax.lax.map(one_block, jnp.arange(0, T + pad, block))
    return a.transpose(0, 3, 1, 2, 4).reshape(T + pad, heads, d)[:T]


def _full_attention(u, lp, d):
    T = u.shape[0]
    heads, kv, hd = d["heads"], d["kv"], d["d"]
    q_gate = (u @ lp["wq"]).reshape(T, heads, 2, hd)
    q, gate = q_gate[:, :, 0], q_gate[:, :, 1]
    k = (u @ lp["wk"]).reshape(T, kv, hd)
    v = (u @ lp["wv"]).reshape(T, kv, hd)
    q = _rope(_zero_centred_norm(q, lp["q_norm_w"], d["eps"]),
              d["rotary"], d["theta"])
    k = _rope(_zero_centred_norm(k, lp["k_norm_w"], d["eps"]),
              d["rotary"], d["theta"])
    a = _grouped_attention(q, k, v) * jax.nn.sigmoid(gate)
    return a.reshape(T, heads * hd) @ lp["wo"]


# -- the feed-forward --------------------------------------------------------

def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _select(h, router_w, d):
    """-> (sel [T, k]: the experts of every token, over all the router's;
    gates [T, k])."""
    probs = jax.nn.softmax(h @ router_w, axis=-1)
    picked, sel = jax.lax.top_k(probs, d["top_k"])
    return sel, picked / jnp.sum(picked, axis=-1, keepdims=True)


def _held_experts_sum(h, router_w, w_gate, w_up, w_down, d):
    """The held experts' terms summed: [T, hidden].  Every held expert is
    computed on EVERY token and weighted by the token's gate for it, exactly
    zero where the token did not choose it: no sort, no capacity."""
    sel, gates = _select(h, router_w, d)
    held = d["first_held"] + jnp.arange(d["held"])
    gate_of = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                gates[:, :, None], 0.0), axis=1)

    def add_expert(y, expert):
        w_g, w_u, w_d, gate = expert
        return y + _swiglu(h, w_g, w_u, w_d) * gate[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (w_gate, w_up, w_down, gate_of.T))
    return y


def _gated_shared(h, lp):
    return jax.nn.sigmoid(h @ lp["shared_expert_gate"]) * _swiglu(
        h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def _mixed(x, lp, kind, d):
    """-> (x after the mixer's residual, the FFN's normed input)."""
    u = _zero_centred_norm(x, lp["ln1_w"], d["eps"])
    mixer = _full_attention if kind == FULL else _linear_mixer
    x = x + mixer(u, lp, d)
    return x, _zero_centred_norm(x, lp["ln2_w"], d["eps"])


@partial(jax.jit, static_argnames=("kind", "dims"))
def _layer(x, lp, *, kind, dims):
    """A layer of `kind` on one sequence, x [T, hidden] float32 -> x (one
    program a kind: the layers of a kind differ in their weights alone)."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        x, h = _mixed(x, lp, kind, d)
        routed = _held_experts_sum(
            h, lp["router_w"], lp["experts_gate"], lp["experts_up"],
            lp["experts_down"], d)
        return x + routed + _gated_shared(h, lp)


@partial(jax.jit, static_argnames=("dims",))
def _rule_alone(x, lp, *, dims):
    """A linear layer's recurrence alone, from the layer's input: its
    operands (q, k by key head, v, g, beta) and its output o."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        u = _zero_centred_norm(x, lp["ln1_w"], d["eps"])
        q, k, v, g, beta, _ = _rule_operands(u, lp, d)
        return (q, k, v, g, beta), _rule_output(q, k, v, g, beta, d)


@partial(jax.jit, static_argnames=("kind", "dims"))
def _layer_back(x, lp, g_x, *, kind, dims):
    """The cotangents of a layer's (x, lp) from that of its output x: the
    layer computed again, then walked back."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(partial(_layer, kind=kind, dims=dims), x, lp)
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
    hidden] (the final norm's output), the last linear layer's
    `gated_delta_rule()` alone and, where `for_grads`, each layer's input
    kept for `grads()`."""

    def __init__(self, params: dict, tokens, dims: dict, for_grads=False):
        self.params, self.dims = params, dims
        self.static = _pairs(dims)
        self.tokens = tokens = jnp.asarray(tokens, jnp.int32)
        self.layers = list(_layers_in_order(params))
        if len(self.layers) != dims["layers"]:
            raise ValueError(f"{len(self.layers)} layers of parameters, "
                             f"num_hidden_layers {dims['layers']}")
        self.last_linear_layer = max(
            i for i, kind in enumerate(dims["kinds"]) if kind == LINEAR)
        x = params["tok_embed"][tokens].astype(F32)
        self.inputs = []
        for layer, where in enumerate(self.layers):
            if for_grads:
                self.inputs.append(x)
            if layer == self.last_linear_layer:
                self.last_linear_input = x
            x = _layer(x, _layer_params(params, where),
                       kind=dims["kinds"][layer], dims=self.static)
        self.last = x
        self.final = _zero_centred_norm(
            x, params["final_norm_w"].astype(F32), dims["eps"])

    def gated_delta_rule(self):
        """The LAST linear layer's recurrence alone: (its operands as the
        program's `gated_delta_rule` takes them, each with a batch axis of
        one: q, k [1, T, H_k, d_k] normalised, v [1, T, H_v, d_v], g, beta
        [1, T, H_v]; the recurrence's output o [1, T, H_v, d_v], step by
        step)."""
        last = self.last_linear_layer
        operands, o = _rule_alone(
            self.last_linear_input,
            _layer_params(self.params, self.layers[last]), dims=self.static)
        return tuple(a[None] for a in operands), o[None]

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
        the head and the final norm first, then the layers from the last
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
        _, pull = jax.vjp(lambda x, w: _zero_centred_norm(x, w, dims["eps"]),
                          self.last, params["final_norm_w"].astype(F32))
        gx, gw = pull(jnp.concatenate(g_final))
        yield ("final_norm_w",), gw
        for layer in reversed(range(len(self.layers))):
            gx, g_lp = _layer_back(
                self.inputs[layer],
                _layer_params(params, self.layers[layer]), gx,
                kind=dims["kinds"][layer], dims=self.static)
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


def whole_layer_ffn(h, lp, d, experts_held, with_shared=True):
    """An expert layer's feed-forward for ANY share of the experts, on its
    normed input: the share's routed sum, and the gated shared expert where
    `with_shared`: what the shares-add-up test sums over the shares
    (the shared expert counted once) and holds against the uncut layer
    (experts_held = (0, router_width))."""
    first, held = experts_held
    with jax.default_matmul_precision("highest"):
        y = _held_experts_sum(
            h, lp["router_w"], lp["experts_gate"], lp["experts_up"],
            lp["experts_down"], {**d, "first_held": first, "held": held})
        return y + _gated_shared(h, lp) if with_shared else y
