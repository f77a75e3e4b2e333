"""Plain reference of the hybrid decoder-decoder model
(Phi-4-mini-flash-reasoning): state-space, sliding-window, full,
gated-memory and cross-attention layers.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, nothing imported from
the program's `models/` or `ops/`.  The recurrence is a sequential
`lax.scan` over time, the convolution is shifted adds, attention is a
masked softmax over the whole key axis, computed one block of queries and
one pair of KV heads at a time so that a sequence of 8192 fits beside a
train state.  `Pass.grads` is the same forward walked back one layer at a
time (each layer's `jax.vjp`), so that the gradient of a sequence of 8192
fits beside a train state too: the recurrence is checkpointed a chunk of
steps at a time and attention a block at a time, which changes what the
backward keeps, not what either computes.  It reads the program's parameter
LAYOUT (`params["layers"][segment][position][name][repeat]`, matrices `[in,
out]`) so that it can be handed the program's own weights.

The equations (each assumed point is listed in the configuration file):

  every layer   h = x + Mixer(LN1(x)); out = h + MLP(LN2(h)); LN with weight
                and bias; MLP(u) = (silu(u Wg) * (u Wu)) Wd
  mamba         [x, z] = u W_in; x = silu(conv_causal_depthwise(x) + b);
                [r, B_t, C_t] = x W_x; dt = softplus(r W_dt + b_dt);
                A = -exp(A_log);
                h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t; y_t = h_t C_t + D x_t;
                out = (y * silu(z)) W_out.  The LAST mamba layer's y is the
                memory m.
  window, full  [q, k, v] = u W_qkv + b; differential attention, causal; a
                window layer's query t sees keys s with 0 <= t - s < window;
                out = a W_o + b_o.  The full layer's k, v are the shared KV.
  gmu           out = (m * silu(u W_1)) W_2
  cross         q = u W_q + b against the shared KV, differential, causal.
  differential  query heads (2i, 2i+1) = (q1, q2); KV heads (2j, 2j+1) =
                (k1, k2); values heads 2j and 2j+1 side by side (2d wide);
                query pair i uses KV pair i // (heads / kv_heads);
                a = softmax(q1 k1^T / sqrt(d)) V - lam softmax(q2 k2^T /
                sqrt(d)) V; RMSNorm over 2d with a weight; x (1 - lam_init);
                lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init;
                lam_init = 0.8 - 0.6 exp(-0.3 l), l the layer's index.
  embedding, the layers, a final LayerNorm, logits = h E^T; no positions.

Departures from the published description: none known in the mathematics;
what could not be confirmed offline is under `assumed` in the configuration.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512       # queries a block of scores holds
LOGIT_ROWS = 1024       # rows of logits the loss holds at a time
SCAN_CHUNK = 128        # steps of the recurrence a backward keeps at a time


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group (the published key names + the layer order and state-space
    sizes)."""
    heads = int(model["num_attention_heads"])
    return {
        "layer_kinds": tuple(model["layer_kinds"]),
        "heads": heads,
        "kv_heads": int(model["num_key_value_heads"]),
        "head_dim": int(model["hidden_size"]) // heads,
        "window": int(model["sliding_window"]),
        "eps": float(model["layer_norm_eps"]),
        "d_state": int(model["mamba_d_state"]),
        "dt_rank": int(model["mamba_dt_rank"]),
    }


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _conv(x, w, b):
    """y_t = b + sum_j w[j] x_{t - (taps - 1) + j}; x: [T, channels]."""
    taps, T = w.shape[0], x.shape[0]
    y = jnp.zeros_like(x) + b
    for j in range(taps):
        shift = taps - 1 - j
        y = y + jnp.concatenate(
            [jnp.zeros((shift, x.shape[1]), F32), x[:T - shift]]) * w[j]
    return y


def _scan_operands(u, lp, d):
    """What the recurrence takes, from the mixer's input: (x, dt, B_t, C_t)
    [T, ...] and the gate z."""
    n, r = d["d_state"], d["dt_rank"]
    x, z = jnp.split(u @ lp["in_proj"], 2, axis=-1)
    x = jax.nn.silu(_conv(x, lp["conv_w"], lp["conv_b"]))
    proj = x @ lp["x_proj"]
    rank, b_t, c_t = proj[:, :r], proj[:, r:r + n], proj[:, r + n:]
    dt = jax.nn.softplus(rank @ lp["dt_w"] + lp["dt_b"])
    return (x, dt, b_t, c_t), z


def _recurrence(x, dt, b_t, c_t, a_log, big_d):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t; y_t = h_t C_t + D x_t."""
    a = -jnp.exp(a_log)

    def step(h, inp):
        x_t, dt_t, bt, ct = inp
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * bt[None]
        return h, h @ ct + big_d * x_t

    # one step after another; in chunks only so that a backward keeps the
    # states of one chunk, not of the sequence (steps padded on with dt = 0
    # and x = 0 leave the state as it is)
    T = x.shape[0]
    pad = -T % SCAN_CHUNK
    chunks = [jnp.pad(v, ((0, pad), (0, 0))).reshape(
        (T + pad) // SCAN_CHUNK, SCAN_CHUNK, v.shape[1])
        for v in (x, dt, b_t, c_t)]
    _, y = jax.lax.scan(jax.checkpoint(partial(jax.lax.scan, step)),
                        jnp.zeros(a.shape, F32), tuple(chunks))
    return y.reshape(T + pad, -1)[:T]


def _mamba(u, lp, d):
    operands, z = _scan_operands(u, lp, d)
    y = _recurrence(*operands, lp["A_log"], lp["D"])
    return (y * jax.nn.silu(z)) @ lp["out_proj"], y


def _differential(q, k, v, lp, layer: int, window, d):
    """q: [T, heads, hd]; k, v: [T, kv_heads, hd] -> [T, heads * hd]."""
    T, heads, hd = q.shape
    kv = k.shape[1]
    per_kv = (heads // 2) // (kv // 2)      # query pairs a KV pair
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"]))
           - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + lam_init)
    # [kv pairs, query pairs of it, first or second head, T, hd]
    qg = q.reshape(T, kv // 2, per_kv, 2, hd).transpose(1, 2, 3, 0, 4)
    kg = k.reshape(T, kv // 2, 2, hd).transpose(1, 2, 0, 3)
    vg = v.reshape(T, kv // 2, 2 * hd).transpose(1, 0, 2)
    key_pos = jnp.arange(T)
    block = min(QUERY_BLOCK, T)
    pad = -T % block
    qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pad), (0, 0)))

    @jax.checkpoint
    def one_block(start):
        q_pos = start + jnp.arange(block)
        behind = q_pos[:, None] - key_pos[None, :]
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)

        @jax.checkpoint
        def one_kv_pair(args):
            qj, kj, vj = args       # [per_kv, 2, block, hd], [2, T, hd], [T, 2hd]
            scores = jnp.einsum("phqd,hkd->phqk", qj, kj) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            out = jnp.einsum("phqk,ke->phqe", probs, vj)
            return out[:, 0] - lam * out[:, 1]          # [per_kv, block, 2hd]

        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=3)
        return jax.lax.map(one_kv_pair, (qb, kg, vg))

    a = jax.lax.map(one_block, jnp.arange(0, T + pad, block))
    # [blocks, kv pairs, per_kv, block, 2hd] -> [T, query pairs, 2hd]
    a = a.transpose(0, 3, 1, 2, 4).reshape(T + pad, heads // 2, 2 * hd)[:T]
    a = a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + d["eps"])
    return (a * lp["subln"] * (1.0 - lam_init)).reshape(T, heads * hd)


@partial(jax.jit, static_argnames=("kind", "layer", "dims"))
def _layer(x, lp, memory, shared_kv, *, kind, layer, dims):
    """One layer on one sequence.  x: [T, hidden] float32."""
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        T = x.shape[0]
        heads, kv, hd = d["heads"], d["kv_heads"], d["head_dim"]
        u = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], d["eps"])
        handed = None
        if kind == "mamba":
            mixed, handed = _mamba(u, lp, d)
        elif kind in ("window", "full"):
            qkv = u @ lp["wqkv"] + lp["bqkv"]
            q = qkv[:, :heads * hd].reshape(T, heads, hd)
            k = qkv[:, heads * hd:(heads + kv) * hd].reshape(T, kv, hd)
            v = qkv[:, (heads + kv) * hd:].reshape(T, kv, hd)
            a = _differential(q, k, v, lp, layer,
                              d["window"] if kind == "window" else None, d)
            mixed = a @ lp["wo"] + lp["bo"]
            if kind == "full":
                handed = (k, v)
        elif kind == "gmu":
            mixed = (memory * jax.nn.silu(u @ lp["w1"])) @ lp["w2"]
        elif kind == "cross":
            q = (u @ lp["wq"] + lp["bq"]).reshape(T, heads, hd)
            a = _differential(q, *shared_kv, lp, layer, None, d)
            mixed = a @ lp["wo"] + lp["bo"]
        else:
            raise ValueError(kind)
        x = x + mixed
        y = _layer_norm(x, lp["ln2_w"], lp["ln2_b"], d["eps"])
        x = x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
            @ lp["w_down"]
        return x, handed


@partial(jax.jit, static_argnames=("dims",))
def _layer_scan_operands(x, lp, *, dims):
    d = dict(dims)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return _scan_operands(
            _layer_norm(x, lp["ln1_w"], lp["ln1_b"], d["eps"]), lp, d)[0]


def _layers_in_order(params: dict):
    """The layers in the model's order, as (segment, position, repeat):
    segments by name, the repeats of a segment, the positions of its
    pattern."""
    for seg_name in sorted(params["layers"]):
        seg = params["layers"][seg_name]
        positions = sorted(seg, key=int)
        repeats = jax.tree.leaves(seg[positions[0]])[0].shape[0]
        for rep in range(repeats):
            for pos in positions:
                yield seg_name, pos, rep


def _layer_params(params: dict, where):
    seg_name, pos, rep = where
    return jax.tree.map(lambda a: a[rep], params["layers"][seg_name][pos])


@partial(jax.jit, static_argnames=("kind", "layer", "dims"))
def _layer_back(x, lp, memory, shared_kv, g_x, g_handed, *, kind, layer,
                dims):
    """The cotangents of a layer's (x, lp, memory, shared_kv) from those of
    its (x, handed): the layer computed again, then walked back.  No
    `g_handed`: nothing read what the layer handed on."""
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(
            partial(_layer, kind=kind, layer=layer, dims=dims),
            x, lp, memory, shared_kv)
        if g_handed is None:
            g_handed = jax.tree.map(jnp.zeros_like, out[1])
        return pull((g_x, g_handed))


@jax.jit
def _head(x, tok_embed):
    with jax.default_matmul_precision("highest"):
        return x @ tok_embed.astype(F32).T


def _rows_nll(x, tok_embed, targets):
    logp = jax.nn.log_softmax(_head(x, tok_embed), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


class Pass:
    """One sequence of T tokens through the layers, float32: `final` [T,
    hidden] (the final LayerNorm's output), `memory` [T, d_inner] (the last
    mamba layer's scan output, None without one), that layer's
    `recurrence()` alone and, where `for_grads`, each layer's inputs kept
    for `grads()`."""

    def __init__(self, params: dict, tokens, dims: dict, for_grads=False):
        kinds = dims["layer_kinds"]
        self.params, self.dims = params, dims
        self.static = tuple(sorted(dims.items()))
        self.tokens = tokens = jnp.asarray(tokens, jnp.int32)
        x = params["tok_embed"][tokens].astype(F32)
        self.memory_source = max(
            (i for i, k in enumerate(kinds) if k == "mamba"), default=None)
        self.memory = shared_kv = None
        self.layers = list(_layers_in_order(params))
        if len(self.layers) != len(kinds):
            raise ValueError(f"{len(self.layers)} layers of parameters, "
                             f"{len(kinds)} kinds")
        self.inputs = []        # (x, memory, shared_kv) of every layer
        for layer, where in enumerate(self.layers):
            if for_grads:
                self.inputs.append((x, self.memory, shared_kv))
            if layer == self.memory_source:
                self.memory_source_input = x
            x, handed = _layer(x, _layer_params(params, where), self.memory,
                               shared_kv, kind=kinds[layer], layer=layer,
                               dims=self.static)
            if layer == self.memory_source:
                self.memory = handed
            elif kinds[layer] == "full":
                shared_kv = handed
        self.last = x
        self.final = _layer_norm(x, params["final_norm_w"].astype(F32),
                                 params["final_norm_b"].astype(F32),
                                 dims["eps"])

    def recurrence(self):
        """The memory source's recurrence alone: (its operands as the
        program's `recurrence` takes them: x, dt, A_log, B_t, C_t, D, with
        a batch axis of one; its output y [1, T, d_inner] = the memory)."""
        lp = _layer_params(self.params, self.layers[self.memory_source])
        x, dt, b_t, c_t = _layer_scan_operands(
            self.memory_source_input, lp, dims=self.static)
        return ((x[None], dt[None], lp["A_log"], b_t[None], c_t[None],
                 lp["D"]), self.memory[None])

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
        at a time so that only one layer's is alive: yields (keys into the
        program's parameters, gradient), the final LayerNorm first, then
        the layers from the last to the first as (("layers", segment,
        position, repeat), {name: gradient}), the embedding last."""
        params, dims, tokens = self.params, self.dims, self.tokens
        targets = jnp.asarray(targets, jnp.int32)
        kinds = dims["layer_kinds"]
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
        _, pull = jax.vjp(
            lambda x, w, b: _layer_norm(x, w, b, dims["eps"]), self.last,
            params["final_norm_w"].astype(F32),
            params["final_norm_b"].astype(F32))
        gx, gw, gb = pull(jnp.concatenate(g_final))
        yield ("final_norm_w",), gw
        yield ("final_norm_b",), gb
        g_memory = g_kv = None      # summed over the layers that read them
        for layer in reversed(range(len(kinds))):
            x, memory, shared_kv = self.inputs[layer]
            g_handed = g_memory if layer == self.memory_source \
                else g_kv if kinds[layer] == "full" else None
            gx, g_lp, gm, gkv = _layer_back(
                x, _layer_params(params, self.layers[layer]), memory,
                shared_kv, gx, g_handed, kind=kinds[layer], layer=layer,
                dims=self.static)
            if memory is not None:
                g_memory = gm if g_memory is None else g_memory + gm
            if shared_kv is not None:
                g_kv = gkv if g_kv is None else jax.tree.map(
                    jnp.add, g_kv, gkv)
            yield ("layers",) + self.layers[layer], g_lp
        yield ("tok_embed",), g_embed.at[tokens].add(gx)


def logits(params: dict, tokens, dims: dict):
    """Full forward pass of ONE sequence: tokens [T] -> logits [T, vocab],
    float32.  Position t sees positions 0..t."""
    return _head(Pass(params, tokens, dims).final, params["tok_embed"])


def token_nll(params: dict, tokens, dims: dict):
    """-log p(tokens[t+1] | tokens[:t+1]) at every position of one
    sequence of S+1 tokens: [S] float32."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return Pass(params, tokens[:-1], dims).token_nll(tokens[1:])


def batch_token_nll(params: dict, batch_tokens, dims: dict):
    """`token_nll` of every row of a batch [B, S+1], one sequence at a
    time: [B, S] float32."""
    return jnp.stack([token_nll(params, row, dims) for row in batch_tokens])


def loss(params: dict, batch_tokens, dims: dict):
    """Mean next-token cross-entropy over a batch [B, S+1]; a float32
    scalar (differentiable in `params`)."""
    return jnp.mean(batch_token_nll(params, batch_tokens, dims))
