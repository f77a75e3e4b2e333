"""Windowed / full grouped-query attention with per-head output gates and a
softmax-routed expert layer (the `laguna` form, as Laguna-S-2.1 publishes
it) for training through `ShardedTrainStep`, on models/stack.py's layer
stack; embedding, cross-entropy and SwiGLU are models/common.py's, the routed
experts models/moe.py's dropless layer, attention ops/attention.py's flash
kernels.

Layer equations (x the layer's input [s, hidden]; every matrix [in, out], no
bias anywhere; layer l has H_l query heads, `num_attention_heads_per_layer`,
over `num_key_value_heads` KV heads of `head_dim`, group g_l = H_l / KV):

  block      x = x + Attn(RMSNorm(x)); x = x + FFN(RMSNorm(x)); eps
             `rms_norm_eps`; a final RMSNorm; logits through an UNTIED head.
  attention  u the normed input.  q = u W_q [s, H_l, d], k = u W_k and v = u
             W_v [s, KV, d].  Rope on q and k by the layer's kind
             (`rope_parameters`): a `sliding_attention` layer turns all d
             dimensions, theta 10,000, pairing (i, i + d/2); a
             `full_attention` layer turns the FIRST d x partial_rotary_factor
             dimensions only, pairing (i, i + that / 2), with yarn's
             frequencies and cos and sin both times `attention_factor`
             (`yarn_inv_freq`), the other dimensions passing through.  Query
             head j reads KV head j // g_l; scores q k^T / sqrt(d), causal;
             in a sliding layer query t sees keys s with 0 <= t - s <
             `sliding_window`.  Per-head gate: G = sigmoid(u W_g) [s, H_l],
             head j's output times G[:, j], then W_o.
  dense FFN  the layers in `mlp_only_layers`: SwiGLU of `intermediate_size`.
  expert FFN p = softmax(y W_r) in float32 over `router_width` experts; a
             token's `num_experts_per_tok` experts are the top of p; gates
             p[sel] / sum(p[sel]) x `moe_routed_scaling_factor`, on the
             experts' OUTPUT; y = sum_e g_e SwiGLU_e(y) + Shared(y), experts
             `moe_intermediate_size` wide, Shared ONE ungated SwiGLU of
             `shared_expert_intermediate_size`.  No auxiliary loss.

One chip's share (`stack.routed_part`): `num_experts` is how many experts
THIS program holds (experts `first_held_expert` on), `router_width` how many
the model routes over; the shared expert is computed whole.

The program.  Parameter shapes differ by kind of layer (W_q, W_g and W_o by
the head count), so the stack is segments: maximal runs of layers of one
kind (attention kind, FFN kind).  The published pattern's first five layers
are three segments: [full + dense], [sliding + experts] x 3, [full +
experts].

How rope reaches the kernels.  Every layer calls `flash_attention(...,
rope=(cos, sin))` on un-roped q and k and the kernels rope the tiles they
load, over the WHOLE head: a sliding layer's rope is exactly that, a full
layer's half rope is made so by W_q's and W_k's columns reordered AT USE
(`stack.rotary_first`; the parameter tree keeps the published order) and
tables with an identity tail (`stack.kernel_tables`), so nothing of rope
stays in XLA (`dispatch.taken()["swa_moe.rope"]` says so).  GQA's repeat of
k and v is the model's, as the kernels' contract has it.

`loss_and_metrics` also gives the LAST expert layer's routing counts
(`moe_rows_held`, `moe_load_max`, `moe_load_mean`, `moe_rows_bound`) and the
rows all the expert layers held together (`moe_rows_held_all_layers`) as
device scalars, which `ShardedTrainStep` carries in the step's metrics.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common, moe, stack
from ray_tpu.models.transformer import rms_norm
from ray_tpu.ops import dispatch
from ray_tpu.parallel.sharding import with_logical_constraint

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# the published Laguna-S-2.1 pattern: a full layer, then three sliding
# ones and a full one eleven times, then three sliding ones
PUBLISHED_LAYER_TYPES = (FULL,) + (SLIDING, SLIDING, SLIDING, FULL) * 11 \
    + (SLIDING,) * 3
PUBLISHED_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


# The usual buffer of an expert layer, in rows even routing would send to
# the held experts.  Only the held experts' terms reach the loss, so their
# router columns are the ones the gradient raises: on the chip the LAST
# expert layer's rows grew from 1.0 x the even load at the first step to 2.1
# x at step 16 and 2.5 x at step 64 (PERF.md, PR 36), and at 2 (the latent
# model's factor, whose share is an eighth where this one is a thirty-
# second) every later step took the bound's buffer there, 36 ms a step.
USUAL_LOAD = 4


def _frozen(x):
    """A JSON value as something hashable (the config is a cache key)."""
    if isinstance(x, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_frozen(v) for v in x)
    return x


@dataclasses.dataclass(frozen=True)
class SwaMoEConfig:
    """The published config.json's key names, the chip's share
    (`router_width`, `first_held_expert`) and the train switches the other
    models have.  The per-layer lists may be given whole: a program of
    `num_hidden_layers` layers runs their first that many."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    mlp_only_layers: Tuple[int, ...] = (0,)
    decoder_sparse_step: int = 1
    sliding_window: int = 512
    rope_parameters: Any = None         # None: the published two tables
    gating: str = "per-head"
    attention_bias: bool = False
    num_experts: int = 256              # held HERE
    router_width: Optional[int] = None  # routed over; None: all are held
    first_held_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0
    moe_apply_router_weight_on_input: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    fused_ce: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        put = functools.partial(object.__setattr__, self)
        if self.router_width is None:
            put("router_width", self.num_experts)
        if len(self.layer_types) < n:
            raise ValueError(f"{len(self.layer_types)} layer_types, "
                             f"num_hidden_layers {n}")
        put("layer_types", tuple(self.layer_types[:n]))
        heads = self.num_attention_heads_per_layer
        if heads is None:
            heads = (self.num_attention_heads,) * n
        if len(heads) < n:
            raise ValueError(f"{len(heads)} head counts, "
                             f"num_hidden_layers {n}")
        put("num_attention_heads_per_layer", tuple(heads[:n]))
        put("mlp_only_layers", tuple(self.mlp_only_layers))
        put("rope_parameters", _frozen(self.rope_parameters or PUBLISHED_ROPE))
        rope = self.rope
        unsupported = {
            "layer_types": bool(set(self.layer_types) - {FULL, SLIDING}),
            "rope_parameters": set(rope) != {FULL, SLIDING} or any(
                r.get("rope_type", "default") not in ("default", "yarn")
                for r in rope.values()),
            "gating": self.gating != "per-head",
            "attention_bias": self.attention_bias,
            "decoder_sparse_step": self.decoder_sparse_step != 1,
            "norm_topk_prob": not self.norm_topk_prob,
            "moe_router_logit_softcapping":
                self.moe_router_logit_softcapping != 0,
            "moe_apply_router_weight_on_input":
                self.moe_apply_router_weight_on_input,
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(f"not written down here, so not computed: {bad}")
        if any(h % self.num_key_value_heads for h in heads[:n]):
            raise ValueError("a layer's query heads must be a multiple of "
                             "the KV heads")
        if self.first_held_expert + self.num_experts > self.router_width:
            raise ValueError("the held experts lie outside the router's")
        for kind in (FULL, SLIDING):
            if self.rotary_width(kind) % 2 or not \
                    0 < self.rotary_width(kind) <= self.head_dim:
                raise ValueError(f"rope pairs dimensions: {kind} turns "
                                 f"{self.rotary_width(kind)} of "
                                 f"{self.head_dim}")

    @property
    def rope(self) -> Dict[str, Dict[str, Any]]:
        return {kind: dict(r) for kind, r in self.rope_parameters}

    def rotary_width(self, kind: str) -> int:
        return int(self.head_dim
                   * self.rope[kind].get("partial_rotary_factor", 1))

    @property
    def experts_held(self) -> Tuple[int, int]:
        return self.first_held_expert, self.num_experts

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, int, str], ...]:
        """(attention kind, query heads, FFN kind) of every layer."""
        return tuple(
            (kind, heads, DENSE if i in self.mlp_only_layers else SPARSE)
            for i, (kind, heads) in enumerate(zip(
                self.layer_types, self.num_attention_heads_per_layer)))

    @classmethod
    def tiny(cls, **kw) -> "SwaMoEConfig":
        """Test-sized: both kinds of layer in the published order, two head
        counts (groups of 2 and 3; heads of 64, two a lane block, so that
        the flash kernels work a pair a program and pad nothing), a window
        shorter than a test's sequence, 4 of 16 experts held."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64,
            num_attention_heads_per_layer=(4, 6, 6, 6, 4), sliding_window=32,
            num_experts=4, router_width=16, num_experts_per_tok=3,
            moe_intermediate_size=32, shared_expert_intermediate_size=32),
            **kw})


def segments(config: SwaMoEConfig) -> List[Tuple[Tuple[str, int, str], int,
                                                 int]]:
    """(kind, first layer, repeats): maximal runs of layers of one kind."""
    return stack.runs(config.layer_kinds)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _layer_shapes(kind: Tuple[str, int, str], c: SwaMoEConfig
                  ) -> Dict[str, Tuple]:
    """name -> (shape, logical axes, init): init is a matrix's fan-in, or
    "ones"."""
    _, heads, ffn = kind
    h, d, kv = c.hidden_size, c.head_dim, c.num_key_value_heads
    shapes = {
        "ln1_w": ((h,), (None,), "ones"),
        "wq": ((h, heads * d), ("embed", "heads"), h),
        "wk": ((h, kv * d), ("embed", "kv"), h),
        "wv": ((h, kv * d), ("embed", "kv"), h),
        "wg": ((h, heads), ("embed", "heads"), h),
        "wo": ((heads * d, h), ("heads", "embed"), heads * d),
        "ln2_w": ((h,), (None,), "ones"),
    }
    if ffn == DENSE:
        return {**shapes, **stack.swiglu_shapes("w", h, c.intermediate_size)}
    return {
        **shapes,
        "router_w": ((h, c.router_width), ("embed", None), h),
        **stack.swiglu_shapes("experts", h, c.moe_intermediate_size,
                              c.num_experts),
        **stack.swiglu_shapes("shared", h,
                              c.shared_expert_intermediate_size)}


def _top_shapes(c: SwaMoEConfig) -> Dict[str, Tuple]:
    table = ((c.vocab_size, c.hidden_size), ("vocab", "embed"), c.hidden_size)
    return {"tok_embed": table, "lm_head": table,
            "final_norm_w": ((c.hidden_size,), (None,), "ones")}


_PARAMS = stack.Params(stack.one_kind(segments), _layer_shapes,
                       _top_shapes)
logical_axes, num_params = _PARAMS.logical_axes, _PARAMS.num_params


def init_params(config: SwaMoEConfig, key) -> Dict[str, Any]:
    """{"tok_embed", "layers": {segNN: {"0": layer parameters stacked on a
    leading repeats axis}}, "final_norm_w", "lm_head" [vocab, hidden]}."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return _PARAMS.init(config, {"tok_embed": k_embed, "lm_head": k_head,
                                 "layers": k_layers})


# ---------------------------------------------------------------------------
# Rope
# ---------------------------------------------------------------------------

def yarn_inv_freq(rope: Dict[str, Any], width: int):
    """The inverse frequencies [width / 2] of a `yarn` table, as Hugging
    Face's `_compute_yarn_parameters` has them for a rotary width of
    `width`: interpolated (/ factor) where a dimension turns fewer than
    `beta_slow` times over the original length, as published where it
    turns more than `beta_fast` times, a linear ramp between (the range's
    ends floored and ceiled, then clamped)."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return (width * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), width - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(width // 2, dtype=F32)
    extrapolated = 1.0 / base ** (2.0 * i / width)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def rope_tables(seq: int, kind: str, c: SwaMoEConfig):
    """(cos, sin) [seq, rotary width / 2] float32 of a kind of layer at
    positions 0 .. seq - 1: the published tables, `attention_factor` in
    both where the kind's rope has one."""
    rope, width = c.rope[kind], c.rotary_width(kind)
    if rope.get("rope_type", "default") == "yarn":
        angle = jnp.arange(seq, dtype=F32)[:, None] \
            * yarn_inv_freq(rope, width)[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
    else:
        cos, sin = stack.rope_tables(seq, width, rope["rope_theta"])
    factor = float(rope.get("attention_factor", 1.0))
    return cos * factor, sin * factor


def kernel_tables(seq: int, kind: str, c: SwaMoEConfig):
    """The tables as the flash kernels take them for a head whose columns
    `_rotary_first_halves` has ordered (`stack.kernel_tables`)."""
    return stack.kernel_tables(*rope_tables(seq, kind, c), c.head_dim)


def _rotary_first_halves(w, heads: int, c: SwaMoEConfig, kind: str):
    """w [hidden, heads x d], a head's columns as published -> as the flash
    kernels' rope pairs them (`stack.rotary_first`); a sliding layer's, all
    rotary, as they are."""
    return stack.rotary_first(w, heads, c.rotary_width(kind))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

_matmul = stack.matmul


def _attention(u, lp, tables, *, kind: str, heads: int, c: SwaMoEConfig):
    """u [b, s, hidden], the normed input -> the mixer's output.  The
    caller's scope (`_layer`) names it: projections, kernels and W_o."""
    from ray_tpu.ops.attention import flash_attention

    b, s, _ = u.shape
    kv, d = c.num_key_value_heads, c.head_dim
    wq = _rotary_first_halves(lp["wq"].astype(c.dtype), heads, c, kind)
    wk = _rotary_first_halves(lp["wk"].astype(c.dtype), kv, c, kind)
    q = with_logical_constraint(_matmul(u, wq, c),
                                ("batch", "seq", "heads"))
    q = q.reshape(b, s, heads, d)
    # the kernels take expanded heads, query head j reading KV head
    # j // group, and take them as the projections lay them, [b, s,
    # heads x d]: the repeat and the gate work on that, by whole tiles
    # (`common.repeat_heads`), and the [b, s, heads, d] views fold away
    k, v = (common.repeat_heads(_matmul(u, w, c), kv, heads // kv)
            .reshape(b, s, heads, d) for w in (wk, lp["wv"]))
    rope = tuple(jnp.broadcast_to(t, (b, *t.shape)) for t in tables)
    a = flash_attention(
        q, k, v, causal=True, sm_scale=1.0 / math.sqrt(d), rope=rope,
        window=c.sliding_window if kind == SLIDING else None)
    with jax.named_scope(common.ATTN_GATE):
        gate = jax.nn.sigmoid(_matmul(u, lp["wg"], c, F32))
        a = common.scale_heads(a.reshape(b, s, heads * d), gate)
    return _matmul(a, lp["wo"], c)


def _routed_part(flat, router_w, w_gate, w_up, w_down, c: SwaMoEConfig):
    """`stack.routed_part` behind the softmax router: flat [T, hidden] ->
    (the held experts' sum, the routing counts)."""
    return stack.routed_part(
        flat, lambda: moe.softmax_route(
            flat, router_w, num_experts_per_token=c.num_experts_per_tok,
            scale=c.moe_routed_scaling_factor),
        w_gate, w_up, w_down, c, USUAL_LOAD)


def routed_experts(h, router_w, w_gate, w_up, w_down, config: SwaMoEConfig):
    """The routed part of an expert layer ALONE, on its operands as the
    layer makes them (h [.., hidden]: the normed input; the router's
    weight; the held experts' weights) -> the held experts' sum, like h:
    the router, the dropless dispatch and the grouped matmuls, no shared
    expert."""
    y, _ = _routed_part(h.reshape(-1, h.shape[-1]), router_w, w_gate, w_up,
                        w_down, config)
    return y.reshape(h.shape)


def _layer(x, lp, tables, *, kind: Tuple[str, int, str], c: SwaMoEConfig):
    """One layer -> (x, the routing counts of an expert layer or None)."""
    attn_kind, heads, ffn_kind = kind
    with jax.named_scope(common.ATTN_FULL if attn_kind == FULL
                         else common.ATTN_SLIDING):
        u = rms_norm(x, lp["ln1_w"], c.rms_norm_eps)
        u = with_logical_constraint(u, ("batch", "seq", "embed"))
        mixed = _attention(u, lp, tables, kind=attn_kind, heads=heads, c=c)
    x = with_logical_constraint(x + mixed, ("batch", "seq", "embed"))
    with jax.named_scope(common.MLP):
        y = rms_norm(x, lp["ln2_w"], c.rms_norm_eps)
    stats = None
    if ffn_kind == DENSE:
        ffn = common.swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"],
                            c.dtype)
    else:
        routed, stats = _routed_part(
            y.reshape(-1, y.shape[-1]), lp["router_w"], lp["experts_gate"],
            lp["experts_up"], lp["experts_down"], c)
        ffn = routed.reshape(y.shape) + common.swiglu(
            y, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
            c.dtype)
    return with_logical_constraint(x + ffn, ("batch", "seq", "embed")), stats


def forward_hidden(params: Dict[str, Any], tokens, config: SwaMoEConfig):
    """Embedding + layers + final RMSNorm: [b, s] -> ([b, s, hidden], the
    LAST expert layer's routing counts and the rows all the expert layers
    held together, or None without one)."""
    c = config
    x = common.embed_tokens(params["tok_embed"], tokens, c.dtype)
    tables = {}
    for kind in sorted(set(c.layer_types)):     # the tables are attention's
        with jax.named_scope(common.ATTN_FULL if kind == FULL
                             else common.ATTN_SLIDING):
            tables[kind] = kernel_tables(tokens.shape[1], kind, c)
    dispatch.record("swa_moe.rope", ",".join(
        stack.rope_word(kind, c.rotary_width(kind), c.head_dim)
        for kind in tables))
    x, stats = stack.walk(_layer, c, segments(c), params["layers"], x,
                          lambda kind: tables[kind[0]])
    with jax.named_scope(common.LOSS):
        return rms_norm(x, params["final_norm_w"], c.rms_norm_eps), stats


_TAIL = stack.LossTail(forward_hidden, head="lm_head")
token_nll, loss_and_metrics = _TAIL.token_nll, _TAIL.loss_and_metrics
loss_fn = _TAIL.loss_fn
