"""Plain reference of the dense block: RMSNorm, RoPE (half-split rotation,
as the published Llama-family code applies it), causal multi-head attention
with optional grouped KV heads, SwiGLU, tied output head, no biases.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no batching
tricks, nothing imported from the program's `models/` or `ops/`.  It reads
the program's parameter LAYOUT (names and `[in, out]` matrices, blocks
stacked on a leading layer axis) so that it can be handed a replica's own
weights; it upcasts each layer's slice as it uses it, so a model served in
bfloat16 never has to exist twice on the device.

Departures from the published description: none in the mathematics.  The
sizes come as a plain dict (`dims`), see `dims_from_config`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims_from_config(model: dict) -> dict:
    """The sizes the reference needs, from a configuration file's `model`
    group (the published key names)."""
    heads = int(model["num_attention_heads"])
    hidden = int(model["hidden_size"])
    return {
        "heads": heads,
        "kv_heads": int(model.get("num_key_value_heads", heads)),
        "head_dim": int(model.get("head_dim") or hidden // heads),
        "rope_theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
    }


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def _rope(x, positions, theta):
    # x: [T, heads, head_dim]; rotate (x1, x2) halves by position * inv_freq.
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                   "rope_theta", "eps"))
def _layer(x, lp, positions, *, heads, kv_heads, head_dim, rope_theta, eps):
    """One block on one sequence.  x: [T, hidden] float32."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        T = x.shape[0]
        y = _rms_norm(x, lp["attn_norm"], eps)
        q = (y @ lp["wq"]).reshape(T, heads, head_dim)
        k = (y @ lp["wk"]).reshape(T, kv_heads, head_dim)
        v = (y @ lp["wv"]).reshape(T, kv_heads, head_dim)
        q, k = _rope(q, positions, rope_theta), _rope(k, positions, rope_theta)
        if kv_heads != heads:
            k = jnp.repeat(k, heads // kv_heads, axis=1)
            v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
        causal = positions[None, :] <= positions[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(T, heads * head_dim)
        x = x + attn @ lp["wo"]
        y = _rms_norm(x, lp["mlp_norm"], eps)
        x = x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]
        return x


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, tok_embed, *, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, final_norm.astype(F32), eps)
        return x @ tok_embed.astype(F32).T


def logits(params: dict, tokens, dims: dict):
    """Full forward pass of ONE sequence: tokens [T] -> logits [T, vocab],
    float32.  Position t attends to positions 0..t."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["tok_embed"][tokens].astype(F32)
    blocks = params["blocks"]
    n_layers = blocks["wq"].shape[0]
    for l in range(n_layers):
        x = _layer(x, {k: v[l] for k, v in blocks.items()}, positions, **dims)
    return _head(x, params["final_norm"], params["tok_embed"], eps=dims["eps"])


def nll_sum(params: dict, tokens, dims: dict):
    """Sum over positions of -log p(tokens[t+1] | tokens[:t+1]) for one
    sequence of S+1 tokens, and the count S."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lg = logits(params, tokens[:-1], dims)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.sum(picked), tokens.shape[0] - 1


def loss(params: dict, batch_tokens, dims: dict) -> float:
    """Mean next-token cross-entropy over a batch [B, S+1], one sequence
    at a time (the logits of one sequence are what the device must hold)."""
    total, count = 0.0, 0
    for row in batch_tokens:
        s, n = nll_sum(params, row, dims)
        total += float(s)
        count += n
    return total / count
