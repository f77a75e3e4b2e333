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

# The step program's parts, named once: every model file and the train
# step put their code under these with `jax.named_scope`, so that the
# compiled program's `op_name` says which part an instruction belongs to
# (the backward and remat's second forward keep the name inside
# `transpose(jvp(..))` / `checkpoint/..`).  A scope is metadata: it moves
# no fusion.  `device_stats.program_report` hands the names to whoever
# joins a device trace to the program (`scripts/opsdump.py --parts`, the
# benchmark's `part_ms.*` readers), and the innermost name decides.
ATTN_FULL = "attn.full"          # causal attention over the whole triangle:
ATTN_SLIDING = "attn.sliding"    # .. or a window; the pre-norm, the
ATTN_CROSS = "attn.cross"        # projections, the kernels, W_o
ATTN_GATE = "attn.gate"          # a per-head output gate, inside one of them
MLA_PROJECT = "mla.project"      # latent attention's projections, likewise
SSM = "ssm"                      # a recurrent mixer: state-space or linear
                                 # attention; projections, conv, the kernels
GMU = "gmu"                      # a gated memory unit that is its own layer
MLP = "mlp"                      # dense and SHARED feed-forward, its pre-norm
MOE_ROUTE = "moe.route"
MOE_DISPATCH = "moe.dispatch"
MOE_EXPERTS = "moe.experts"
MOE_COMBINE = "moe.combine"
EMBED = "embed"
LOSS = "loss"                    # final norm, head, cross-entropy
OPTIMIZER = "optimizer"          # everything of the step behind the gradient
SCOPES = (ATTN_FULL, ATTN_SLIDING, ATTN_CROSS, ATTN_GATE, MLA_PROJECT, SSM,
          GMU, MLP, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE, EMBED,
          LOSS, OPTIMIZER)
# A residual stream of several lanes: the calls round a sublayer that mix
# them (ops/hyper_connection.py) and the collapse behind the stack, OUTSIDE
# every sublayer's own scope.  A part of the step of its own, but NOT in
# SCOPES yet: whoever tiles the step gives every name of SCOPES a bucket
# (benchmark/part_lib.py, which tests/test_program_report.py holds to this
# tuple), and until the tiling has one for it these operations are the
# tiling's `unscoped`; a reader that wants them alone finds the name in
# `op_name` (`residual_mix_ms`).
RESID_MIX = "resid.mix"
# A name INSIDE one of the scopes above, which decides no part: what
# compressed convolutional attention does between its projections and the
# kernels (the shifted value half, both convolutions, the q-k mean, the l2
# norm and temperature).  Its operations stay `attn.full`'s for whoever
# tiles the step by SCOPES; a reader that wants them alone finds the name
# in `op_name`.
ATTN_MIX = "attn.mix"
# Likewise inside `ssm`: a linear mixer's chain between W_qkv's product and
# the rule's kernels (ops/mixer_chain.py: conv, SiLU, the l2 norms, the cut
# into q, k, v) and, in the backward, the sum over a key head's value heads.
SSM_CHAIN = "ssm.chain"


def embed_tokens(tok_embed, tokens, dtype):
    with jax.named_scope(EMBED):
        x = tok_embed.astype(dtype)[tokens]
        return with_logical_constraint(x, ("batch", "seq", "embed"))


def tied_logits(x, tok_embed, dtype):
    """Logits of the weight-tied head from normed hidden states (bf16
    operands, fp32 accumulation: the MXU's native mode — an fp32xfp32
    einsum here ran at half rate for ~10% of the model's FLOPs)."""
    with jax.named_scope(LOSS):
        logits = jnp.einsum(
            "bsh,vh->bsv", x.astype(dtype), tok_embed.astype(dtype),
            preferred_element_type=jnp.float32)
        return with_logical_constraint(logits, ("batch", "seq", "vocab"))


def swiglu(y, w_gate, w_up, w_down, dtype):
    """(silu(y Wg) * (y Wu)) Wd, no bias."""
    with jax.named_scope(MLP):
        gate = jax.nn.silu(y @ w_gate.astype(dtype))
        up = y @ w_up.astype(dtype)
        ffn = with_logical_constraint(gate * up, ("batch", "seq", "mlp"))
        return checkpoint_name(ffn @ w_down.astype(dtype), "mlp_out")


def relu2(x):
    """relu(x)^2, squared in float32 and rounded once to x's dtype."""
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(x.dtype)


def relu2_mlp(y, w_up, w_down, dtype):
    """relu(y Wu)^2 Wd, no gate matrix, no bias."""
    with jax.named_scope(MLP):
        ffn = with_logical_constraint(relu2(y @ w_up.astype(dtype)),
                                      ("batch", "seq", "mlp"))
        return checkpoint_name(ffn @ w_down.astype(dtype), "mlp_out")


def causal_depthwise_conv(x, w, b=None):
    """y_t = b + sum_j w[j] x_{t - (taps - 1) + j}: `taps` shifted adds
    over the time axis, nothing before position 0.  x: [b, s, channels];
    w: [taps, channels]; b: [channels] or None."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = 0.0 if b is None else b.astype(x.dtype)
    for j in range(taps):
        y = y + padded[:, j:j + s] * w[j].astype(x.dtype)
    return y


# Heads beside each other on the last axis, [b, s, heads x d], is how a
# projection's matmul writes q, k and v, how the flash kernels take them
# and how W_o reads the output (ops/attention.py).  On the TPU such an
# array lies in tiles of 8 rows x 128 columns, and a [b, s, heads, d] view
# of it is ANOTHER arrangement (its tiles run over heads x d), which XLA
# reaches by a copy of the whole array.  So what a model does to single
# heads between the projections and the kernels it does to whole tiles,
# [b, s / 8, heads, 8, d]: for d a multiple of 128 the two transposes
# below move no byte, and a repeat compiles to one broadcast (PERF.md,
# PR 38; 8 is the tile's rows for float32 and bfloat16 alike: with 16 or
# 32 the copies come back).  Any other d or s is as right, and as XLA
# lays it.
_TILE_ROWS = 8


def by_tiles(x, heads: int):
    """[b, s, heads x w] -> [b, s / 8, heads, 8, w] (s / 1 and 1 where 8
    does not divide s)."""
    b, s, _ = x.shape
    rows = _TILE_ROWS if s % _TILE_ROWS == 0 else 1
    return x.reshape(b, s // rows, rows, heads, -1).transpose(0, 1, 3, 2, 4)


def from_tiles(x):
    """[b, s / 8, heads, 8, w] -> [b, s, heads x w]."""
    b, blocks, _, rows, _ = x.shape
    return x.transpose(0, 1, 3, 2, 4).reshape(b, blocks * rows, -1)


def repeat_heads(x, heads: int, group: int):
    """GQA's repeat on a projection's output: x [b, s, heads x d] -> [b, s,
    heads x group x d], head j of the result the head j // group of x
    (`jnp.repeat(.., group, axis=2)` of the [b, s, heads, d] view)."""
    if group == 1:
        return x
    t = by_tiles(x, heads)
    t = jnp.broadcast_to(t[:, :, :, None],
                         (*t.shape[:3], group, *t.shape[3:]))
    return from_tiles(t.reshape(*t.shape[:2], heads * group, *t.shape[4:]))


def scale_heads(x, scale):
    """x [b, s, heads x d] with head j's d columns times scale[.., j]
    ([b, s, heads], float32): the product in float32, rounded to x's
    dtype."""
    heads = scale.shape[-1]
    t = by_tiles(scale, heads)                          # [.., heads, 8, 1]
    wide = from_tiles(jnp.broadcast_to(
        t, (*t.shape[:4], x.shape[-1] // heads)))
    return (x.astype(jnp.float32) * wide).astype(x.dtype)


def masked_mean(nll, mask):
    with jax.named_scope(LOSS):
        if mask is None:
            return jnp.mean(nll)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def _fused_nll_flat(hidden, tok_embed, targets):
    """[b * s] NLL through the fused chunked cross-entropy
    (ops/fused_ce.py): the fp32 [tokens, vocab] logits never exist."""
    from ray_tpu.ops.fused_ce import fused_ce_nll

    b, s = targets.shape
    with jax.named_scope(LOSS):
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
    with jax.named_scope(LOSS):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None],
                                    axis=-1)[..., 0]


def fused_nll(hidden, tok_embed, targets):
    """Per-token next-token NLL [b, s] through the fused chunked
    cross-entropy, from normed hidden states [b, s, h]."""
    return _fused_nll_flat(hidden, tok_embed, targets).reshape(targets.shape)


# what "save_attn" keeps of a layer: the flash kernels' outputs, named in
# ops/attention._flash_fwd as the kernel wrote them
SAVE_ATTN_NAMES = ("attn_out", "attn_lse")


def maybe_remat(block_fn, remat: bool, remat_policy: str):
    """`block_fn` under `jax.checkpoint`, keeping across it what the policy
    names: "full" nothing but the layer's input, "save_attn" also the
    flash kernels' out and lse, "dots" / "dots_no_mlp" matmul outputs.
    `ShardedTrainStep` reads "full" as "keep what fits": it builds the
    step under "save_attn" first and falls back to "full" where the
    compiled program does not fit the device (train/train_state.py)."""
    if not remat:
        return block_fn
    if remat_policy == "dots":
        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if remat_policy == "save_attn":
        # Middle ground between "full" (recompute everything, min HBM)
        # and "dots" (save every matmul, OOMs at billion scale): keep
        # only the flash kernel's outputs (out + lse) so the backward
        # re-derives the cheap projections but never re-runs the
        # attention kernel.
        # .. nor, in a layer that is a state-space-dual mixer, the
        # recurrence's forward kernel (its y and its blocks' first states;
        # a layer without one has no such name, and its program does not
        # move)
        from ray_tpu.ops.ssd_scan import KEPT_NAMES

        return jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                *SAVE_ATTN_NAMES, *KEPT_NAMES))
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
