"""What the model files share: the embedding lookup, the tied output head,
the next-token loss (plain or fused chunked cross-entropy), the SwiGLU
feed-forward and the per-layer remat wrapper.  One copy, so that a change
to the head or to remat reaches every architecture the benchmark trains.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.parallel.sharding import with_logical_constraint


def embed_tokens(tok_embed, tokens, dtype):
    x = tok_embed.astype(dtype)[tokens]
    return with_logical_constraint(x, ("batch", "seq", "embed"))


def tied_logits(x, tok_embed, dtype):
    """Logits of the weight-tied head from normed hidden states (bf16
    operands, fp32 accumulation: the MXU's native mode — an fp32xfp32
    einsum here ran at half rate for ~10% of the model's FLOPs)."""
    logits = jnp.einsum(
        "bsh,vh->bsv", x.astype(dtype), tok_embed.astype(dtype),
        preferred_element_type=jnp.float32)
    return with_logical_constraint(logits, ("batch", "seq", "vocab"))


def swiglu(y, w_gate, w_up, w_down, dtype):
    """(silu(y Wg) * (y Wu)) Wd, no bias."""
    gate = jax.nn.silu(y @ w_gate.astype(dtype))
    up = y @ w_up.astype(dtype)
    ffn = with_logical_constraint(gate * up, ("batch", "seq", "mlp"))
    return checkpoint_name(ffn @ w_down.astype(dtype), "mlp_out")


def masked_mean(nll, mask):
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def _fused_nll_flat(hidden, tok_embed, targets):
    """[b * s] NLL through the fused chunked cross-entropy
    (ops/fused_ce.py): the fp32 [tokens, vocab] logits never exist."""
    from ray_tpu.ops.fused_ce import fused_ce_nll

    b, s = targets.shape
    return fused_ce_nll(hidden.reshape(b * s, -1), tok_embed,
                        targets.reshape(-1))


def fused_ce(hidden, tok_embed, targets, mask):
    """Mean next-token NLL from normed hidden states [b, s, h] through the
    fused chunked cross-entropy.  mask: [b, s] over the targets, or None."""
    return masked_mean(_fused_nll_flat(hidden, tok_embed, targets),
                       None if mask is None else mask.reshape(-1))


def logits_ce(logits, targets, mask):
    """Mean next-token NLL from fp32 logits [b, s, vocab]."""
    return masked_mean(logits_nll(logits, targets), mask)


def logits_nll(logits, targets):
    """Per-token next-token NLL [b, s] from fp32 logits [b, s, vocab]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def fused_nll(hidden, tok_embed, targets):
    """Per-token next-token NLL [b, s] through the fused chunked
    cross-entropy, from normed hidden states [b, s, h]."""
    return _fused_nll_flat(hidden, tok_embed, targets).reshape(targets.shape)


def maybe_remat(block_fn, remat: bool, remat_policy: str):
    if not remat:
        return block_fn
    if remat_policy == "dots":
        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if remat_policy == "save_attn":
        # Middle ground between "full" (recompute everything, min HBM)
        # and "dots" (save every matmul, OOMs at billion scale): keep
        # only the flash kernel's outputs (out + lse, named in
        # ops/attention.py _flash_lse_fwd) so the backward re-derives
        # the cheap projections but never re-runs the attention kernel.
        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse"))
    if remat_policy == "dots_no_mlp":
        # "dots" minus its biggest buffers: save every matmul output
        # EXCEPT the gate/up MLP intermediates ([b, s, intermediate] —
        # 4x the hidden-size tensors), which the backward recomputes
        # from the saved layer input.  ~40% of dots' activation memory
        # for ~0.6N of the 2N recompute "full" pays — the policy that
        # fits billion-class models at useful batch sizes.
        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_q", "attn_k", "attn_v", "attn_out", "attn_lse",
                "attn_proj", "mlp_out"))
    if remat_policy == "full":
        return jax.checkpoint(block_fn)
    raise ValueError(f"unknown remat_policy {remat_policy!r}; expected "
                     "'full', 'dots', 'save_attn' or 'dots_no_mlp'")
