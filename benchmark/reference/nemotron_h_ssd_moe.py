"""Plain reference of the Mamba-2 / attention / routed-expert decoder whose
layers are ONE sublayer each, in the published `nemotron_h` form (NVIDIA-
Nemotron-3-Nano-30B-A3B: `model_type: nemotron_h`).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no chunks, nothing
imported from the program's `models/` or `ops/`.  The mamba layers'
recurrence is walked ONE STEP AT A TIME (`lax.scan` over time; the steps are
grouped by 64 under `jax.checkpoint` only so that a backward holds one state
a group and not one a step: nothing of the chunked algebra is here).  The
convolution is explicit shifted sums.  Attention is a masked softmax over
the whole key axis, a block of queries and one KV head's group of query
heads at a time.  The routed experts are computed an expert at a time on
every token, with a mask.  `Pass.grads` is the same forward walked back one
layer at a time (each layer's `jax.vjp`).  It reads the program's parameter
LAYOUT (`params["layers"][segment]["0"][name][repeat]`, matrices `[in,
out]`) so that it can be handed the program's own weights.

The equations.  n(x; w) = x / sqrt(mean(x^2) + eps) w, a plain RMSNorm (eps
`layer_norm_epsilon`).  Layer l of kind pattern[l] (`M`, `*`, `E`): x <- x +
f_l(n(x; ln_w)), ONE sublayer a layer.  u = n(x; ln_w) [T, hidden]:

  M  1. [z | xBC | dt~] = u W_in, widths d_inner | d_inner + 2 G N | H, in
        that order, no bias; d_inner = H P (`mamba_num_heads` x
        `mamba_head_dim`), NOT `expand` x hidden.
     2. xBC <- silu(conv(xBC) + conv_b): y_t = sum_j w[j] x_{t - 3 + j} over
        `conv_kernel` = 4 taps, causal, a channel at a time, rows before 0
        zero.  x = xBC[:, :d_inner] as [T, H, P], B = the next G N columns
        as [T, G, N], C the last G N; head h reads group h // (H / G).
     3. dt = softplus(dt~ + dt_bias) [T, H], no clamp; a = -exp(A_log) [H].
     4. a head, S [P, N] from zero, t = 0 ..: S <- exp(dt_t a) S + dt_t x_t
        (x) B_t; y_t = S C_t + D x_t.
     5. y <- y silu(z), THEN over each of G groups of d_inner / G channels
        apart: y / sqrt(mean(y^2) + eps) w (gn_w [d_inner]).
     6. f = y W_out.
  *  q = u W_q [T, heads, d], k = u W_k, v = u W_v [T, KV, d]; no bias, NO
     positional encoding, no norm; query head j reads KV head j // (heads /
     KV); scores q k^T / sqrt(d), causal; softmax; f = concat(a_j) W_o.
  E  s = sigmoid(u W_r) over `router_width`; the top `num_experts_per_tok`
     of s + b; gates s[sel] / (sum s[sel] + 1e-20) x
     `routed_scaling_factor`; f = sum over the chosen experts HELD HERE of
     gate_e relu(u W_up_e)^2 W_down_e + relu(u W_su)^2 W_sd.
  end  n(x; final_norm_w), untied head, mean next-token cross-entropy.

The share.  The parameters hold `held` experts, experts `first_held` on, of
the `router_width` the router scores.  The router and the top k run over all
of them; only the held experts' terms are summed (and the shared expert);
what the other experts would add is left out, here as in the program.

Assumptions (each with its reason under `assumed` in the configuration):
one sublayer a layer, the order of W_in's columns, d_inner from the heads,
the gate before the grouped norm, no positional encoding, the router's
form, the ungated relu^2 expert, no auxiliary loss.
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
MAMBA, FULL, EXPERTS = "M", "*", "E"


def _pairs(x):
    return tuple(sorted(x.items()))


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group (the published key names + the chip's share)."""
    pattern = str(model["hybrid_override_pattern"])
    if len(pattern) != int(model["num_hidden_layers"]):
        raise ValueError(f"{model['num_hidden_layers']} layers, the pattern "
                         f"{pattern!r}")
    held = int(model["n_routed_experts"])
    return {
        "layers": len(pattern), "kinds": tuple(pattern),
        "H": int(model["mamba_num_heads"]), "P": int(model["mamba_head_dim"]),
        "G": int(model["n_groups"]), "N": int(model["ssm_state_size"]),
        "heads": int(model["num_attention_heads"]),
        "kv": int(model["num_key_value_heads"]),
        "d": int(model["head_dim"]),
        "eps": float(model["layer_norm_epsilon"]),
        "top_k": int(model["num_experts_per_tok"]),
        "scale": float(model["routed_scaling_factor"]),
        "held": held,
        "first_held": int(model.get("first_held_expert", 0)),
        "router_width": int(model.get("router_width") or held),
    }


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


# -- the mamba layer ---------------------------------------------------------

def _causal_conv(x, w, b):
    """x [T, channels], w [taps, channels], b [channels]: y_t = b + sum_j
    w[j] x_{t - (taps - 1) + j}, rows before 0 zero."""
    taps, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return b + sum(padded[j:j + T] * w[j] for j in range(taps))


def _recurrence(x, dt, a, B, C, D):
    """Step 4, every head: x [T, H, P], dt [T, H], a and D [H], B, C [T, H,
    N] (a head's group's) -> y [T, H, P]."""
    T, heads, P = x.shape

    def step(S, inp):                   # S [H, P, N]
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * a)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + D[:, None] * x_t

    @jax.checkpoint
    def group(S, inp):
        return jax.lax.scan(step, S, inp)

    pad = -T % STEPS_A_GROUP
    grouped = tuple(
        jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
            (T + pad) // STEPS_A_GROUP, STEPS_A_GROUP, *v.shape[1:])
        for v in (x, dt, B, C))         # a padded step: dt 0, x 0
    _, y = jax.lax.scan(group, jnp.zeros((heads, P, B.shape[-1]), F32),
                        grouped)
    return y.reshape(T + pad, heads, P)[:T]


def _scan_operands(u, lp, d):
    """Steps 1-3 of a mamba layer: (x [T, H, P], dt [T, H], a [H], B, C by
    GROUP [T, G, N], D [H]; z [T, d_inner])."""
    T = u.shape[0]
    H, P, G, N = d["H"], d["P"], d["G"], d["N"]
    inner = H * P
    wide = inner + 2 * G * N
    proj = u @ lp["w_in"]
    z, xBC, dt = (proj[:, :inner], proj[:, inner:inner + wide],
                  proj[:, inner + wide:])
    xBC = jax.nn.silu(_causal_conv(xBC, lp["conv_w"], lp["conv_b"]))
    x = xBC[:, :inner].reshape(T, H, P)
    B = xBC[:, inner:inner + G * N].reshape(T, G, N)
    C = xBC[:, inner + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    return (x, dt, -jnp.exp(lp["A_log"]), B, C, lp["D"]), z


def _scan_output(x, dt, a, B, C, D, d):
    per = d["H"] // d["G"]
    return _recurrence(x, dt, a, jnp.repeat(B, per, axis=1),
                       jnp.repeat(C, per, axis=1), D)


def _mamba_mixer(u, lp, d):
    T = u.shape[0]
    operands, z = _scan_operands(u, lp, d)
    y = _scan_output(*operands, d).reshape(T, -1) * jax.nn.silu(z)
    y = _norm(y.reshape(T, d["G"], -1), lp["gn_w"].reshape(d["G"], -1),
              d["eps"])
    return y.reshape(T, -1) @ lp["w_out"]


# -- the attention layer -----------------------------------------------------

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
    a = _grouped_attention((u @ lp["wq"]).reshape(T, heads, hd),
                           (u @ lp["wk"]).reshape(T, kv, hd),
                           (u @ lp["wv"]).reshape(T, kv, hd))
    return a.reshape(T, heads * hd) @ lp["wo"]


# -- the expert layer --------------------------------------------------------

def _relu2_mlp(h, w_up, w_down):
    return jnp.square(jax.nn.relu(h @ w_up)) @ w_down


def _select(h, router_w, router_bias, d):
    """-> (sel [T, k]: the experts of every token, over all the router's;
    gates [T, k])."""
    scores = jax.nn.sigmoid(h @ router_w)
    _, sel = jax.lax.top_k(scores + jax.lax.stop_gradient(router_bias),
                           d["top_k"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, (picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
                 * d["scale"])


def _held_experts_sum(h, router_w, router_bias, w_up, w_down, d):
    """The held experts' terms summed: [T, hidden].  Every held expert is
    computed on EVERY token and weighted by the token's gate for it, exactly
    zero where the token did not choose it: no sort, no capacity."""
    sel, gates = _select(h, router_w, router_bias, d)
    held = d["first_held"] + jnp.arange(d["held"])
    gate_of = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                gates[:, :, None], 0.0), axis=1)

    def add_expert(y, expert):
        w_u, w_d, gate = expert
        return y + _relu2_mlp(h, w_u, w_d) * gate[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (w_up, w_down, gate_of.T))
    return y


def _expert_ffn(u, lp, d):
    return (_held_experts_sum(u, lp["router_w"], lp["router_bias"],
                              lp["experts_up"], lp["experts_down"], d)
            + _relu2_mlp(u, lp["shared_up"], lp["shared_down"]))


_SUBLAYER = {MAMBA: _mamba_mixer, FULL: _full_attention, EXPERTS: _expert_ffn}


@partial(jax.jit, static_argnames=("kind", "dims"))
def _layer(x, lp, *, kind, dims):
    """A layer of `kind` on one sequence, x [T, hidden] float32 -> x (one
    program a kind: the layers of a kind differ in their weights alone)."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + _SUBLAYER[kind](_norm(x, lp["ln_w"], d["eps"]), lp, d)


@partial(jax.jit, static_argnames=("dims",))
def _scan_alone(x, lp, *, dims):
    """A mamba layer's recurrence alone, from the layer's input: its
    operands (x, dt, a, B and C by group, D) and its output y."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        operands, _ = _scan_operands(_norm(x, lp["ln_w"], d["eps"]), lp, d)
        return operands, _scan_output(*operands, d)


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
    hidden] (the final norm's output), the last mamba layer's `ssd_scan()`
    alone and, where `for_grads`, each layer's input kept for `grads()`."""

    def __init__(self, params: dict, tokens, dims: dict, for_grads=False):
        self.params, self.dims = params, dims
        self.static = _pairs(dims)
        self.tokens = tokens = jnp.asarray(tokens, jnp.int32)
        self.layers = list(_layers_in_order(params))
        if len(self.layers) != dims["layers"]:
            raise ValueError(f"{len(self.layers)} layers of parameters, "
                             f"num_hidden_layers {dims['layers']}")
        self.last_mamba_layer = max(
            i for i, kind in enumerate(dims["kinds"]) if kind == MAMBA)
        x = params["tok_embed"][tokens].astype(F32)
        self.inputs = []
        for layer, where in enumerate(self.layers):
            if for_grads:
                self.inputs.append(x)
            if layer == self.last_mamba_layer:
                self.last_mamba_input = x
            x = _layer(x, _layer_params(params, where),
                       kind=dims["kinds"][layer], dims=self.static)
        self.last = x
        self.final = _norm(x, params["final_norm_w"].astype(F32), dims["eps"])

    def ssd_scan(self):
        """The LAST mamba layer's recurrence alone: (its operands as the
        program's `ssd_scan` takes them, those over time with a batch axis
        of one: x [1, T, H, P], dt [1, T, H], a [H], B, C [1, T, G, N], D
        [H]; the recurrence's output y [1, T, H, P], step by step)."""
        last = self.last_mamba_layer
        (x, dt, a, B, C, D), y = _scan_alone(
            self.last_mamba_input,
            _layer_params(self.params, self.layers[last]), dims=self.static)
        return (x[None], dt[None], a, B[None], C[None], D), y[None]

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
        _, pull = jax.vjp(lambda x, w: _norm(x, w, dims["eps"]),
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
    normed input: the share's routed sum, and the shared expert where
    `with_shared`: what the shares-add-up test sums over the shares (the
    shared expert counted once) and holds against the uncut layer
    (experts_held = (0, router_width))."""
    first, held = experts_held
    with jax.default_matmul_precision("highest"):
        y = _held_experts_sum(
            h, lp["router_w"], lp["router_bias"], lp["experts_up"],
            lp["experts_down"], {**d, "first_held": first, "held": held})
        return y + _relu2_mlp(h, lp["shared_up"], lp["shared_down"]) \
            if with_shared else y
