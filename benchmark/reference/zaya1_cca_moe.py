"""Plain reference of the compressed-convolutional-attention decoder with a
top-1 expert layer behind an MLP router that carries its state from layer to
layer, in the published `zaya` form (ZAYA1-8B: `model_type: zaya`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, nothing imported from
the program's `models/` or `ops/`.  The convolutions are explicit shifted
sums.  Attention is a masked softmax over the whole key axis, a block of
queries and one KV head's group of query heads at a time.  The experts are
computed an expert at a time on every token, under a mask.  `Pass.grads` is
the same forward walked back one layer at a time (each layer's `jax.vjp`),
the cotangent of the router's state walked back beside the stream's.  It
reads the program's parameter LAYOUT (`params["layers"][segment]["0"][name]
[repeat]`, matrices `[in, out]`) so that it can be handed the program's own
weights.

The equations (E hidden, H_q query heads over H_kv KV heads of d, group G =
H_q / H_kv, latents L_q = H_q d and L_kv = H_kv d, R the router's width; N(x;
w) = x / sqrt(mean(x^2) + eps) w, a PLAIN RMSNorm, eps `rms_norm_eps`).  A
sublayer f with pre-norm n updates the stream as x <- (s_r x + b_r) + (s_h
f(n(x)) + b_h), s and b vectors of E.  x [s, E]:

  attention 1. h = N(x; ln1_w).  q~ = h W_q [s, L_q], k~ = h W_k [s, L_kv].
            2. v = [h W_v1 | shift(h) W_v2], shift(h)_t = h_(t-1), 0 at t =
               0; the L_kv columns cut into the H_kv heads in order.
            3. c = [q~ | k~]; c1_t = sum_j a_j c_(t - (taps0 - 1) + j) + b_1
               a channel; c2_t = sum_j c1_(t - (taps1 - 1) + j) B_j^(head) +
               b_2, B [d, d] a tap a head (H_q + H_kv heads); rows before 0
               are zero.
            4. Q~, K~ the heads of q~, k~; M_q = (Q~ + K~ repeated G times)
               / 2; M_k[g] = mean of M_q over group g's G query heads; Q =
               heads(c2[:, :L_q]) + M_q, K = heads(c2[:, L_q:]) + M_k.
            5. Q <- Q / sqrt(sum Q^2 + 1e-6) sqrt(d); K likewise x tau_g.
            6. rope on the FIRST r = d x `partial_rotary_factor` columns of
               Q and K: angle t x theta^(-2i/r), pairing (i, i + r/2); query
               head j reads KV head j // G; scores q k^T / sqrt(d), causal;
               softmax; o = softmax x v.
            7. f = concat(o) W_o, W_o [L_q, E].
  experts   h = N(x; ln2_w).  r = h W_rd + b_rd; layer l > 0: r <- r +
            alpha r_prev (the previous layer's r after its own such step);
            z = gelu(gelu(N(r; w_n) W_1 + b_1) W_2 + b_2) W_3, gelu the exact
            one (erf); p = softmax(z) over `router_width`; sel = the top
            `num_experts_per_tok` of p + beta (beta under stop_gradient);
            gates p[sel], NOT renormalised.  f = sum over the chosen experts
            HELD HERE of p[sel] (silu(h Wg_e) * (h Wu_e)) Wd_e.
  end       N(x; final_norm_w), logits x W_emb^T (the tied embedding), mean
            next-token cross-entropy.

The share.  The parameters hold `held` experts, experts `first_held` on, of
the `router_width` the router scores.  The router and the selection run over
all of them; only the held experts' terms are summed; what the other experts
would add is left out, here as in the program.

Assumptions (each with its reason under `assumed` in the configuration): the
residual scaling's form, plain norms, the shifted value half, the two
convolutions' forms, the q-k mean, the l2 norm's eps and temperature, the
router's MLP, its carry and the raw gate, no auxiliary loss, no skip expert.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # queries a block of scores holds
LOGIT_ROWS = 1024       # rows of logits the loss holds at a time
L2_EPS = 1e-6
HYBRID = "hybrid"


def _pairs(x):
    return tuple(sorted(x.items()))


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group (the published key names + the chip's share)."""
    held = int(model["num_experts"])
    rope = model["rope_parameters"][HYBRID]
    return {
        "layers": int(model["num_hidden_layers"]),
        "heads": int(model["num_attention_heads"]),
        "kv": int(model["num_key_value_heads"]),
        "d": int(model["head_dim"]),
        "rotary": int(int(model["head_dim"])
                      * float(rope["partial_rotary_factor"])),
        "theta": float(rope["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "top_k": int(model["num_experts_per_tok"]),
        "held": held,
        "first_held": int(model.get("first_held_expert", 0)),
        "router_width": int(model.get("router_width") or held),
    }


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _shift(x, back: int):
    """x [T, ..] -> row t holds x's row t - back; rows before 0 are zero."""
    if back == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:back]), x[:x.shape[0] - back]])


# -- the attention sublayer ----------------------------------------------------

def _mix(u, lp, d):
    """Steps 1-5: the normed input u [T, E] -> (Q [T, H_q, d], K, V [T,
    H_kv, d]): l2-normalised, before rope."""
    T = u.shape[0]
    heads, kv, hd = d["heads"], d["kv"], d["d"]
    group = heads // kv
    q0, k0 = u @ lp["wq"], u @ lp["wk"]
    v = jnp.concatenate([u @ lp["wv1"], _shift(u, 1) @ lp["wv2"]], axis=-1)
    c = jnp.concatenate([q0, k0], axis=-1)
    taps0 = lp["conv0_w"].shape[0]
    c1 = sum(lp["conv0_w"][j] * _shift(c, taps0 - 1 - j)
             for j in range(taps0)) + lp["conv0_b"]
    taps1 = lp["conv1_w"].shape[1]
    c1 = c1.reshape(T, heads + kv, hd)
    c2 = sum(jnp.einsum("thd,hde->the", _shift(c1, taps1 - 1 - j),
                        lp["conv1_w"][:, j])
             for j in range(taps1)) + lp["conv1_b"].reshape(heads + kv, hd)
    q_heads, k_heads = q0.reshape(T, heads, hd), k0.reshape(T, kv, hd)
    mean_q = (q_heads + jnp.repeat(k_heads, group, axis=1)) / 2.0
    mean_k = jnp.mean(mean_q.reshape(T, kv, group, hd), axis=2)
    q, k = c2[:, :heads] + mean_q, c2[:, heads:] + mean_k

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS) \
            * math.sqrt(hd)

    return l2(q), l2(k) * lp["tau"][None, :, None], v.reshape(T, kv, hd)


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


def _attention(u, lp, d):
    q, k, v = _mix(u, lp, d)
    q, k = (_rope(x, d["rotary"], d["theta"]) for x in (q, k))
    return _grouped_attention(q, k, v).reshape(u.shape[0], -1) @ lp["wo"]


# -- the expert sublayer -------------------------------------------------------

def _router(h, r_prev, lp, d, first: bool):
    """-> (sel [T, k]: the experts of every token, over all the router's;
    gates [T, k]; r [T, R], handed to the next layer)."""
    r = h @ lp["router_down_w"] + lp["router_down_b"]
    if not first:
        r = r + lp["router_carry"] * r_prev
    a = _norm(r, lp["router_norm_w"], d["eps"])
    a = jax.nn.gelu(a @ lp["router_w1"] + lp["router_b1"], approximate=False)
    a = jax.nn.gelu(a @ lp["router_w2"] + lp["router_b2"], approximate=False)
    probs = jax.nn.softmax(a @ lp["router_w3"], axis=-1)
    _, sel = jax.lax.top_k(
        probs + jax.lax.stop_gradient(lp["router_bias"]), d["top_k"])
    return sel, jnp.take_along_axis(probs, sel, axis=-1), r


def _held_experts_sum(h, sel, gates, w_gate, w_up, w_down, d):
    """The held experts' terms summed: [T, E].  Every held expert is
    computed on EVERY token and weighted by the token's gate for it, exactly
    zero where the token did not choose it: no sort, no capacity."""
    held = d["first_held"] + jnp.arange(d["held"])
    gate_of = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                gates[:, :, None], 0.0), axis=1)

    @jax.checkpoint         # a backward holds one sum an expert, no more
    def add_expert(y, expert):
        w_g, w_u, w_d, gate = expert
        out = (jax.nn.silu(h @ w_g) * (h @ w_u)) @ w_d
        return y + out * gate[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (w_gate, w_up, w_down, gate_of.T))
    return y


def _residual(x, f, lp, prefix):
    return (lp[prefix + "_sr"] * x + lp[prefix + "_br"]) \
        + (lp[prefix + "_sh"] * f + lp[prefix + "_bh"])


@partial(jax.jit, static_argnames=("first", "dims"))
def _layer(x, r_prev, lp, *, first, dims):
    """A layer on one sequence: (x [T, E], r_prev [T, R]) float32 -> (x,
    r); `first`: the layer that takes no state."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        x = _residual(x, _attention(_norm(x, lp["ln1_w"], d["eps"]), lp, d),
                      lp, "attn")
        h = _norm(x, lp["ln2_w"], d["eps"])
        sel, gates, r = _router(h, r_prev, lp, d, first)
        f = _held_experts_sum(h, sel, gates, lp["experts_gate"],
                              lp["experts_up"], lp["experts_down"], d)
        return _residual(x, f, lp, "ffn"), r


MIX_OPERANDS = ("wq", "wk", "wv1", "wv2", "conv0_w", "conv0_b", "conv1_w",
                "conv1_b", "tau")


@partial(jax.jit, static_argnames=("dims",))
def _mix_alone(x, lp, *, dims):
    """A layer's steps 1-5 alone, from the layer's input: the normed input
    and [Q | K | V] [T, L_q + 2 L_kv]."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        u = _norm(x, lp["ln1_w"], d["eps"])
        T = u.shape[0]
        return u, jnp.concatenate(
            [a.reshape(T, -1) for a in _mix(u, lp, d)], axis=-1)


@partial(jax.jit, static_argnames=("first", "dims"))
def _layer_back(x, r_prev, lp, g_x, g_r, *, first, dims):
    """The cotangents of a layer's (x, r_prev, lp) from those of its
    outputs (x, r): the layer computed again, then walked back."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(partial(_layer, first=first, dims=dims),
                          x, r_prev, lp)
        return pull((g_x, g_r))


@jax.jit
def _head(x, tok_embed):
    with jax.default_matmul_precision("highest"):
        return x @ tok_embed.astype(F32).T


def _rows_nll(x, tok_embed, targets):
    logp = jax.nn.log_softmax(_head(x, tok_embed), axis=-1)
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
    """One sequence of T tokens through the layers, float32: `final` [T, E]
    (the final norm's output), the last layer's `cca_mix()` alone and, where
    `for_grads`, each layer's inputs kept for `grads()`."""

    def __init__(self, params: dict, tokens, dims: dict, for_grads=False):
        self.params, self.dims = params, dims
        self.static = _pairs(dims)
        self.tokens = tokens = jnp.asarray(tokens, jnp.int32)
        self.layers = list(_layers_in_order(params))
        if len(self.layers) != dims["layers"]:
            raise ValueError(f"{len(self.layers)} layers of parameters, "
                             f"num_hidden_layers {dims['layers']}")
        x = params["tok_embed"][tokens].astype(F32)
        width = _layer_params(params, self.layers[0])["router_down_b"].shape
        r = jnp.zeros((x.shape[0],) + width, F32)
        self.inputs = []
        for layer, where in enumerate(self.layers):
            if for_grads:
                self.inputs.append((x, r))
            self.last_input = x
            x, r = _layer(x, r, _layer_params(params, where),
                          first=layer == 0, dims=self.static)
        self.last = x
        self.final = _norm(x, params["final_norm_w"].astype(F32),
                           dims["eps"])

    def cca_mix(self):
        """The LAST layer's steps 1-5 alone: (its operands as the program's
        `cca_mix` takes them: the normed input [1, T, E] and that layer's
        nine weights, float32; [Q | K | V] [1, T, L_q + 2 L_kv], a head's
        columns in the published order, before rope)."""
        lp = _layer_params(self.params, self.layers[-1])
        u, want = _mix_alone(self.last_input, lp, dims=self.static)
        return (u[None],) + tuple(lp[n].astype(F32)
                                  for n in MIX_OPERANDS), want[None]

    def token_nll(self, targets):
        """-log p(targets[t] | tokens[:t+1]) at every position: [T]; the
        logits a block of rows at a time."""
        targets = jnp.asarray(targets, jnp.int32)
        return jnp.concatenate([
            _rows_nll(self.final[start:start + LOGIT_ROWS],
                      self.params["tok_embed"],
                      targets[start:start + LOGIT_ROWS])
            for start in range(0, self.final.shape[0], LOGIT_ROWS)])

    def grads(self, targets):
        """The gradient of mean(token_nll(targets)), walked back one layer
        at a time: yields (keys into the program's parameters, gradient),
        the final norm first, then the layers from the last to the first as
        (("layers", segment, position, repeat), {name: gradient}), the tied
        embedding last (the head's share and the lookup's together)."""
        params, dims, tokens = self.params, self.dims, self.tokens
        targets = jnp.asarray(targets, jnp.int32)
        embed = params["tok_embed"]
        steps = self.final.shape[0]
        g_final, g_embed = [], jnp.zeros(embed.shape, F32)
        for start in range(0, steps, LOGIT_ROWS):
            gx, ge = jax.grad(
                lambda x, e, t: jnp.sum(_rows_nll(x, e, t)) / steps, (0, 1))(
                self.final[start:start + LOGIT_ROWS], embed,
                targets[start:start + LOGIT_ROWS])
            g_final.append(gx)
            g_embed = g_embed + ge
        _, pull = jax.vjp(lambda x, w: _norm(x, w, dims["eps"]),
                          self.last, params["final_norm_w"].astype(F32))
        gx, gw = pull(jnp.concatenate(g_final))
        yield ("final_norm_w",), gw
        gr = jnp.zeros_like(self.inputs[0][1])  # nothing reads the last r
        for layer in reversed(range(len(self.layers))):
            x, r = self.inputs[layer]
            gx, gr, g_lp = _layer_back(
                x, r, _layer_params(params, self.layers[layer]), gx, gr,
                first=layer == 0, dims=self.static)
            yield ("layers",) + self.layers[layer], g_lp
        yield ("tok_embed",), g_embed.at[tokens].add(gx)


def token_nll(params: dict, tokens, dims: dict):
    """-log p(tokens[t+1] | tokens[:t+1]) at every position of one
    sequence of S+1 tokens: [S] float32."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return Pass(params, tokens[:-1], dims).token_nll(tokens[1:])


def batch_token_nll(params: dict, batch_tokens, dims: dict):
    """`token_nll` of every row of a batch [B, S+1], one sequence at a
    time: [B, S] float32."""
    return jnp.stack([token_nll(params, row, dims) for row in batch_tokens])


def whole_layer_ffn(h, r_prev, lp, d, experts_held, first=False):
    """An expert sublayer's sum for ANY share of the experts, on its normed
    input and the router's state: what the shares-add-up test sums over the
    shares and holds against the uncut layer (experts_held = (0,
    router_width))."""
    first_held, held = experts_held
    with jax.default_matmul_precision("highest"):
        sel, gates, _ = _router(h, r_prev, lp, d, first)
        return _held_experts_sum(
            h, sel, gates, lp["experts_gate"], lp["experts_up"],
            lp["experts_down"], {**d, "first_held": first_held, "held": held})
