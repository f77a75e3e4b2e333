"""Flagship decoder-only transformer (Llama family), TPU-first.

Pure-JAX (no flax dependency in the hot path): params are plain pytrees with
logical-axis annotations consumed by parallel/sharding.py.  Design choices
that matter on TPU:

  - scan-over-layers with `jax.checkpoint` (remat): one compiled layer body,
    weights stacked on a leading "layers" axis → fast compiles, HBM-friendly.
  - bfloat16 activations, fp32 RMSNorm accumulation and logits.
  - GQA (num_kv_heads <= num_heads), RoPE, SwiGLU — the Llama recipe.
  - every matmul annotated via with_logical_constraint so GSPMD places
    DP/FSDP/TP/SP collectives (SURVEY.md §2.4 targets).

Reference parity note: the reference (Ray) ships no model code — its LLM
release tests wrap HF models (release/release_tests.yaml:842–1015).  Our
framework is the model runtime too, so the flagship model lives in-tree.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import common
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    with_logical_constraint,
)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # Remat policy: "full" recomputes the whole layer (min memory),
    # "dots" saves matmul outputs and recomputes only cheap elementwise
    # ops (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) —
    # higher MFU when HBM allows, since the MXU work isn't re-done.
    remat_policy: str = "full"
    scan_layers: bool = True
    use_flash: bool = True  # ops.flash_attention pallas kernel when on TPU
    # Sequence/context parallelism: ring attention over the mesh "seq"
    # axis (ops/ring_attention.py).  "auto" uses it iff the ambient mesh
    # shards seq; True forces; False never.
    ring_attention: Any = "auto"
    # Fused chunked cross-entropy (ops/fused_ce.py): never materializes
    # the fp32 [tokens, vocab] logits — frees the GBs that let
    # recompute-free remat policies fit HBM.  Training-loss path only;
    # forward() still produces real logits for inference.
    fused_ce: bool = False
    # Mixture-of-experts: num_experts > 0 replaces the dense FFN with a
    # top-k routed expert FFN (models/moe.py) on the "expert" mesh axis.
    num_experts: int = 0
    num_experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def llama2_7b(cls, **kw) -> "TransformerConfig":
        return cls(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_layers=32, num_heads=32, num_kv_heads=32, max_seq_len=4096,
        ), **kw})

    @classmethod
    def tiny(cls, **kw) -> "TransformerConfig":
        """Test-sized config: compiles in seconds on CPU."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
        ), **kw})


# ---------------------------------------------------------------------------
# Param init.  Layout (scan_layers=True): block params stacked on axis 0.
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, fan_in):
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def init_params(config: TransformerConfig, key) -> Dict[str, Any]:
    c = config
    hd = c.head_dim_
    keys = jax.random.split(key, 8)
    pd = c.param_dtype

    def block_shape(shape):
        return (c.num_layers, *shape) if c.scan_layers else shape

    def init_block(k, shape, fan_in):
        if c.scan_layers:
            ks = jax.random.split(k, c.num_layers)
            return jnp.stack([
                _dense_init(ks[i], shape, pd, fan_in)
                for i in range(c.num_layers)])
        return _dense_init(k, shape, pd, fan_in)

    h, m = c.hidden_size, c.intermediate_size
    blocks = {
        "attn_norm": jnp.ones(block_shape((h,)), pd),
        "wq": init_block(keys[1], (h, c.num_heads * hd), h),
        "wk": init_block(keys[2], (h, c.num_kv_heads * hd), h),
        "wv": init_block(keys[3], (h, c.num_kv_heads * hd), h),
        "wo": init_block(keys[4], (c.num_heads * hd, h), c.num_heads * hd),
        "mlp_norm": jnp.ones(block_shape((h,)), pd),
    }
    if c.num_experts > 0:
        E = c.num_experts
        blocks["router"] = init_block(keys[5], (h, E), h)
        blocks["we_gate"] = init_block(keys[6], (E, h, m), h)
        blocks["we_up"] = init_block(keys[7], (E, h, m), h)
        blocks["we_down"] = init_block(
            jax.random.fold_in(keys[7], 1), (E, m, h), m)
    else:
        blocks["w_gate"] = init_block(keys[5], (h, m), h)
        blocks["w_up"] = init_block(keys[6], (h, m), h)
        blocks["w_down"] = init_block(keys[7], (m, h), m)
    params = {
        "tok_embed": _dense_init(keys[0], (c.vocab_size, h), pd, h),
        "blocks": blocks,
        "final_norm": jnp.ones((h,), pd),
    }
    return params


def logical_axes(config: TransformerConfig) -> Dict[str, Any]:
    """Logical-axis tree matching init_params, for parallel.sharding rules."""
    L = ("layers",) if config.scan_layers else ()
    blocks = {
        "attn_norm": L + (None,),
        "wq": L + ("embed", "heads"),
        "wk": L + ("embed", "heads"),
        "wv": L + ("embed", "heads"),
        "wo": L + ("heads", "embed"),
        "mlp_norm": L + (None,),
    }
    if config.num_experts > 0:
        blocks.update({
            "router": L + ("embed", None),
            "we_gate": L + ("expert", "embed", "mlp"),
            "we_up": L + ("expert", "embed", "mlp"),
            "we_down": L + ("expert", "mlp", "embed"),
        })
    else:
        blocks.update({
            "w_gate": L + ("embed", "mlp"),
            "w_up": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed"),
        })
    return {
        "tok_embed": ("vocab", "embed"),
        "blocks": blocks,
        "final_norm": (None,),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dtype) * weight.astype(dtype)


def rope_freqs(head_dim: int, max_len: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [max_len, head_dim//2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, positions):
    # x: [b, s, heads, hd]; cos/sin: [max_len, hd//2]; positions: [b, s]
    from ray_tpu.ops.attention import rope_reference

    return rope_reference(x, cos[positions], sin[positions])


def _use_ring(config: TransformerConfig) -> bool:
    if config.ring_attention is True:
        return True
    if config.ring_attention == "auto":
        import jax as _jax

        mesh = _jax.sharding.get_abstract_mesh()
        return (mesh is not None and not mesh.empty
                and "seq" in mesh.axis_names
                and mesh.shape.get("seq", 1) > 1)
    return False


def _ropes_in_flash(config: TransformerConfig) -> bool:
    """True when `_attention` will call flash_attention, whose kernels rope
    q and k themselves (`rope=`): `_block` then hands them as projected."""
    return config.use_flash and not _use_ring(config)


def _attention(q, k, v, mask, config: TransformerConfig, rope=None):
    """q:[b,s,h,hd] k,v:[b,s,kv,hd] causal attention with GQA.  rope: the
    tables at the rows' positions when q and k are not roped yet (only
    where `_ropes_in_flash`); rope after GQA's repeat gives the same
    values."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if _use_ring(config):
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, causal=True)
    if config.use_flash:
        from ray_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, causal=True, rope=rope)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block(x, bp, rope, mask, config: TransformerConfig):
    """rope: (cos, sin) gathered at the rows' positions, [b, s, hd//2],
    once for all layers by the caller."""
    c = config
    hd = c.head_dim_
    b, s, h = x.shape

    with jax.named_scope(common.ATTN_FULL):
        y = rms_norm(x, bp["attn_norm"], c.rms_eps)
        y = with_logical_constraint(y, ("batch", "seq", "embed"))
        # The heads' constraint goes on the projections' own [b, s, heads x hd]
        # (whole heads a shard, as on the 4-D view): behind it the reshape to
        # heads folds against the flash kernels' own back, and q and k reach
        # them as the matmuls wrote them; on the 4-D view it stood between the
        # two and XLA laid that view out, sequence-minor, and copied it.
        q, k, v = (with_logical_constraint(y @ bp[w].astype(c.dtype),
                                           ("batch", "seq", "heads"))
                   for w in ("wq", "wk", "wv"))
        q = q.reshape(b, s, c.num_heads, hd)
        k = k.reshape(b, s, c.num_kv_heads, hd)
        v = v.reshape(b, s, c.num_kv_heads, hd)
        if not _ropes_in_flash(c):
            from ray_tpu.ops.attention import rope_reference

            q, k = rope_reference(q, *rope), rope_reference(k, *rope)
            rope = None
        attn = _attention(q, k, v, mask, c, rope)
        attn = attn.reshape(b, s, c.num_heads * hd)
        attn_proj = checkpoint_name(
            attn @ bp["wo"].astype(c.dtype), "attn_proj")
    x = x + attn_proj
    x = with_logical_constraint(x, ("batch", "seq", "embed"))

    with jax.named_scope(common.MLP):
        y = rms_norm(x, bp["mlp_norm"], c.rms_eps)
    if c.num_experts > 0:
        from ray_tpu.models.moe import moe_ffn

        with jax.named_scope(common.MOE_EXPERTS):
            out2d, aux = moe_ffn(
                y.reshape(b * s, h), bp["router"], bp["we_gate"],
                bp["we_up"], bp["we_down"],
                num_experts_per_token=c.num_experts_per_token,
                capacity_factor=c.capacity_factor, dtype=c.dtype)
        x = x + out2d.reshape(b, s, h)
    else:
        aux = jnp.zeros((), jnp.float32)
        x = x + common.swiglu(y, bp["w_gate"], bp["w_up"], bp["w_down"],
                              c.dtype)
    return with_logical_constraint(x, ("batch", "seq", "embed")), aux


def _embed_tokens(params, tokens, c: TransformerConfig):
    return common.embed_tokens(params["tok_embed"], tokens, c.dtype)


def _lm_head(params, x, c: TransformerConfig):
    """Final norm + weight-tied head."""
    with jax.named_scope(common.LOSS):
        x = rms_norm(x, params["final_norm"], c.rms_eps)
    return common.tied_logits(x, params["tok_embed"], c.dtype)


def _maybe_remat(block_fn, c: TransformerConfig):
    return common.maybe_remat(block_fn, c.remat, c.remat_policy)


def forward_hidden(params: Dict[str, Any], tokens,
                   config: TransformerConfig, positions=None):
    """Embed + layer stack + final RMSNorm (no lm head): returns
    (x_normed [b, s, h], moe_aux).  The fused-CE training path consumes
    this directly (ops/fused_ce.py)."""
    c = config
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed_tokens(params, tokens, c)
    with jax.named_scope(common.ATTN_FULL):     # the tables are attention's
        cos, sin = rope_freqs(c.head_dim_, c.max_seq_len, c.rope_theta)
        rope = (cos[positions], sin[positions])
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))[None, None, :, :]

    block_fn = _maybe_remat(
        partial(_block, rope=rope, mask=mask, config=c), c)

    aux_total = jnp.zeros((), jnp.float32)
    if c.scan_layers:
        def scan_body(carry, layer_params):
            y, aux = block_fn(carry[0], layer_params)
            return (y, carry[1] + aux), None

        (x, aux_total), _ = jax.lax.scan(
            scan_body, (x, aux_total), params["blocks"])
    else:
        x, aux_total = block_fn(x, params["blocks"])
    with jax.named_scope(common.LOSS):
        return rms_norm(x, params["final_norm"], c.rms_eps), aux_total


def forward(params: Dict[str, Any], tokens, config: TransformerConfig,
            positions=None, return_aux: bool = False):
    """tokens: [b, s] int32 → logits [b, s, vocab] (fp32).

    With return_aux=True also returns the MoE router load-balance loss
    (zero for dense models)."""
    c = config
    x, aux_total = forward_hidden(params, tokens, c, positions)
    logits = common.tied_logits(x, params["tok_embed"], c.dtype)
    if return_aux:
        return logits, aux_total
    return logits


def forward_pipelined(params: Dict[str, Any], tokens,
                      config: TransformerConfig, num_stages: int,
                      num_microbatches: Optional[int] = None,
                      mesh=None):
    """GPipe-pipelined forward over the mesh "stage" axis.

    Capability the reference lacks entirely (SURVEY.md §2.4 — Ray has no
    in-tree PP).  The layer stack splits into `num_stages` contiguous
    runs; microbatch activations hop stages via ppermute inside ONE
    jitted program (parallel/pipeline.py), and the embed/LM-head ends
    run replicated across stages.  Differentiable end-to-end, so
    ShardedTrainStep trains through it directly.  Composes with
    data/fsdp axes (they stay under GSPMD); ring attention (seq axis)
    is mutually exclusive with PP for now.
    """
    from ray_tpu.parallel.pipeline import pipeline_apply

    c = config
    if not c.scan_layers:
        raise ValueError("pipelined forward requires scan_layers=True")
    if c.num_experts > 0:
        raise ValueError("pipelined forward does not support MoE yet")
    ring_on = c.ring_attention is True or (
        c.ring_attention == "auto" and mesh is not None
        and dict(mesh.shape).get("seq", 1) > 1)
    if ring_on:
        raise ValueError("pipelined forward does not compose with ring "
                         "attention yet (use seq=1 with stage>1)")
    if c.num_layers % num_stages:
        raise ValueError(
            f"{c.num_layers} layers not divisible by {num_stages} stages")
    b, s = tokens.shape
    x = _embed_tokens(params, tokens, c)
    cos, sin = rope_freqs(c.head_dim_, c.max_seq_len, c.rope_theta)
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))[None, None, :, :]

    def stage_fn(stage_blocks, xm):
        # xm: one microbatch's activations [mb, s, h]; stage_blocks
        # leaves [L/S, ...] (this stage's contiguous layers).
        mb = xm.shape[0]
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (mb, s))
        block = _maybe_remat(
            partial(_block, rope=(cos[positions], sin[positions]),
                    mask=mask, config=c), c)

        def scan_body(carry, layer_params):
            y, _aux = block(carry, layer_params)
            return y, None

        y, _ = jax.lax.scan(scan_body, xm, stage_blocks)
        return y

    from ray_tpu.parallel.pipeline import stack_stage_params

    stacked = stack_stage_params(params["blocks"], num_stages)
    x = pipeline_apply(stage_fn, stacked, x, mesh=mesh,
                       num_microbatches=num_microbatches)
    return _lm_head(params, x, c)


def loss_fn_pipelined(params, batch, config: TransformerConfig,
                      num_stages: int,
                      num_microbatches: Optional[int] = None,
                      mesh=None):
    """Next-token cross-entropy through the pipelined forward."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = forward_pipelined(params, inputs, config, num_stages,
                               num_microbatches, mesh=mesh)
    mask = batch.get("mask")
    return common.logits_ce(logits, targets,
                            None if mask is None else mask[:, 1:])


def loss_fn(params, batch, config: TransformerConfig):
    """Next-token cross-entropy (+ router aux loss for MoE models).
    batch: {"tokens": [b, s+1] int32}."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    if config.fused_ce:
        x, aux = forward_hidden(params, inputs, config)
        ce = common.fused_ce(x, params["tok_embed"], targets, mask)
    else:
        logits, aux = forward(params, inputs, config, return_aux=True)
        ce = common.logits_ce(logits, targets, mask)
    if config.num_experts > 0:
        ce = ce + config.router_aux_coef * aux / config.num_layers
    return ce


def num_params(config: TransformerConfig) -> int:
    c = config
    hd = c.head_dim_
    per_layer = (c.hidden_size * (c.num_heads * hd)
                 + 2 * c.hidden_size * (c.num_kv_heads * hd)
                 + (c.num_heads * hd) * c.hidden_size
                 + 3 * c.hidden_size * c.intermediate_size
                 + 2 * c.hidden_size)
    return (c.vocab_size * c.hidden_size + c.num_layers * per_layer
            + c.hidden_size)
