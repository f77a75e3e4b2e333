"""Latent-attention, routed-expert decoder (the DeepSeek-V3 form, as
Kanana-2-30B-A3B publishes it) for training through `ShardedTrainStep`, on
models/stack.py's layer stack; embedding, cross-entropy and SwiGLU are
models/common.py's, the routed experts models/moe.py's dropless layer.

Layer equations (h the layer's input, T tokens; every matrix [in, out], no
bias anywhere):

  block      x = x + Attn(RMSNorm(x)); x = x + FFN(RMSNorm(x)); eps
             `rms_norm_eps`; a final RMSNorm; logits through an UNTIED head.
  attention  q = h W_q -> [T, heads, nope + rope] = [q_nope | q_pe];
             c = h W_kva -> [T, kv_lora_rank + rope]; c_kv = RMSNorm(c[:rank])
             with a weight, k_pe = c[rank:] (ONE head, shared by all);
             c_kv W_kvb -> [T, heads, nope + v] = [k_nope | v];
             rope (theta `rope_theta`, the interleaved pairing (2i, 2i + 1))
             on q_pe and k_pe only; k = [k_nope | k_pe];
             causal softmax(q k^T / sqrt(nope + rope)) v -> [T, heads, v];
             W_o: heads x v -> hidden.  Keys are nope + rope wide (192),
             values v wide (128): ONE call of the flash kernels, which take
             q, [k_nope | v] and k_pe as the projections lay them and rope
             and put the keys together themselves (`_attention`).
  dense FFN  the first `first_k_dense_replace` layers: SwiGLU of
             `intermediate_size`.
  expert FFN s = sigmoid(h W_r) in float32 over `router_width` experts; a
             token's `num_experts_per_tok` experts are the top of s + b
             (b: the selection bias, `noaux_tc`; one group, no group limit);
             gates g = s[sel] / sum(s[sel]) x `routed_scaling_factor` (the
             bias is not in the gate); y = sum_e g_e (silu(h Wg_e) * (h
             Wu_e)) Wd_e + Shared(h), experts `moe_intermediate_size` wide,
             Shared ONE SwiGLU of `n_shared_experts` x that width.  No
             auxiliary loss.

The form Xing4.0-29B-A4B publishes adds three things, each a field:
  a QUERY latent  `q_lora_rank`: q = RMSNorm(h W_qa) W_qb, the norm with a
             weight, W_qb's columns laid as W_q's.
  yarn       `rope_scaling` {"type": "yarn", ..}: the inverse frequencies of
             models/swa_moe.py's `yarn_inv_freq` over the rotary dimensions,
             cos and sin times mscale(factor, `mscale`) / mscale(factor,
             `mscale_all_dim`), the softmax scale times mscale(factor,
             `mscale_all_dim`)^2, mscale(f, m) = 0.1 m ln f + 1 (Hugging
             Face's DeepSeek-V3 arithmetic).
  lanes      `hc_mult` n: the residual stream is n lanes [b, s, n hidden],
             each the embedding at first, mixed per token round EVERY
             sublayer by ops/hyper_connection.py's two calls with the
             sublayer's own leaves (`stack.residual`), and collapsed behind
             the last layer by a per-token weighted sum (`hc_collapse`).
  a second loss  `num_nextn_predict_layers` 1: h'_i = W_eh [RMSNorm(embed(
             t_{i+1})) ; RMSNorm(x_i)] (x_i the collapsed stream BEFORE the
             final norm), lanes h' again, ONE expert layer with its own
             leaves, its own collapse and final norm, the SHARED embedding
             and head, scoring t_{i+2}; a position's objective is nll_main[i]
             + `MTP_LOSS_WEIGHT` x nll_mtp[i] (`stack.LossTail`'s `second`).
             The block's leaves are the group `params["mtp"]`.

One chip's share (`stack.routed_part`).  `n_routed_experts` is how many
experts THIS program holds (experts `first_held_expert` on), `router_width`
how many the model routes over; the shared expert is computed whole.  With
router_width == n_routed_experts it is the whole layer.

The selection bias `router_bias` is a leaf of the parameters that is not
trained (`not_trained`): it enters the selection only, gets no gradient,
and the step leaves it as it is (its published update rule is a
load-balancing controller outside the optimiser, and its rate is not
published).

The program.  The dense layers are one segment, the expert layers another.
`loss_and_metrics` also gives the LAST expert layer's routing counts
(`moe_rows_held`, `moe_load_max`, `moe_load_mean`, `moe_rows_bound`) and the
rows all the expert layers held together (`moe_rows_held_all_layers`); with
lanes `hc_row_err`, how far that layer's mixing matrices' rows are from
summing to 1; with the second loss `mtp_nll`, its mean.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import common, moe, stack
from ray_tpu.models.swa_moe import _frozen, yarn_inv_freq
from ray_tpu.models.transformer import rms_norm
from ray_tpu.ops import dispatch
from ray_tpu.parallel.sharding import with_logical_constraint

F32 = jnp.float32
# the second loss's weight in the objective: DeepSeek-V3's first phase (its
# report, section 4.2); the published config does not give one
MTP_LOSS_WEIGHT = 0.3


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """The published config.json's key names, the chip's share
    (`router_width`, `first_held_expert`) and the train switches the other
    models have."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128         # held HERE
    router_width: Optional[int] = None  # routed over; None: all are held
    first_held_expert: int = 0
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rope_scaling: Any = None            # None, or yarn's keys
    hc_mult: Optional[int] = None       # lanes of the residual stream
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    num_nextn_predict_layers: int = 0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    fused_ce: bool = False

    def __post_init__(self):
        if self.router_width is None:
            object.__setattr__(self, "router_width", self.n_routed_experts)
        if isinstance(self.rope_scaling, dict):     # the config is a cache key
            object.__setattr__(self, "rope_scaling",
                               _frozen(self.rope_scaling))
        unsupported = {
            "scoring_func": self.scoring_func != "sigmoid",
            "topk_method": self.topk_method != "noaux_tc",
            "n_group / topk_group": (self.n_group, self.topk_group) != (1, 1),
            "norm_topk_prob": not self.norm_topk_prob,
            "rope_interleave": not self.rope_interleave,
            "rope_scaling": self.rope_scaling is not None
            and self.yarn.get("type", self.yarn.get("rope_type")) != "yarn",
            "hc_mult": self.hc_mult is not None and not 2 <= self.hc_mult <= 4,
            "num_nextn_predict_layers":
                self.num_nextn_predict_layers not in (0, 1),
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(f"not written down here, so not computed: {bad}")
        if not 0 < self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace must lie in 1 .. "
                             "num_hidden_layers")
        if self.first_held_expert + self.n_routed_experts > self.router_width:
            raise ValueError("the held experts lie outside the router's")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rope pairs dimensions: qk_rope_head_dim is odd")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def experts_held(self) -> Tuple[int, int]:
        return self.first_held_expert, self.n_routed_experts

    @property
    def yarn(self) -> Dict[str, Any]:
        """`rope_scaling`'s keys, with `rope_theta` as `yarn_inv_freq`
        reads it; empty without one."""
        return {**dict(self.rope_scaling or ()),
                "rope_theta": self.rope_theta} if self.rope_scaling else {}

    @property
    def softmax_scale(self) -> float:
        scale = 1.0 / math.sqrt(self.qk_head_dim)
        if self.yarn.get("mscale_all_dim"):
            scale *= _mscale(self.yarn["factor"],
                             self.yarn["mscale_all_dim"]) ** 2
        return scale

    @classmethod
    def tiny(cls, **kw) -> "LatentMoEConfig":
        """Test-sized: both kinds of layer, a share of the experts."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=2, kv_lora_rank=32, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32, n_routed_experts=4,
            router_width=16, num_experts_per_tok=3), **kw})


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def segments(config: LatentMoEConfig) -> List[Tuple[str, int, int]]:
    """(kind, first layer, repeats): the dense layers, then the expert
    layers."""
    dense = config.first_k_dense_replace
    out = [("dense", 0, dense)]
    if config.num_hidden_layers > dense:
        out.append(("moe", dense, config.num_hidden_layers - dense))
    return out


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _layer_shapes(kind: str, c: LatentMoEConfig) -> Dict[str, Tuple]:
    """name -> (shape, logical axes, init): init is a matrix's fan-in, or
    "ones" or "select_bias"."""
    h, heads = c.hidden_size, c.num_attention_heads
    rank, rope = c.kv_lora_rank, c.qk_rope_head_dim
    q_rank = c.q_lora_rank
    query = {"wq": ((h, heads * c.qk_head_dim), ("embed", "heads"), h)} \
        if q_rank is None else {
        "wq_a": ((h, q_rank), ("embed", None), h),
        "q_norm_w": ((q_rank,), (None,), "ones"),
        "wq_b": ((q_rank, heads * c.qk_head_dim), (None, "heads"), q_rank)}
    lanes = {} if c.hc_mult is None else {
        **stack.hc_shapes("hc_attn", h, c.hc_mult),
        **stack.hc_shapes("hc_ffn", h, c.hc_mult)}
    shapes = {
        "ln1_w": ((h,), (None,), "ones"),
        **query,
        "wkv_a": ((h, rank + rope), ("embed", None), h),
        "kv_norm_w": ((rank,), (None,), "ones"),
        "wkv_b": ((rank, heads * (c.qk_nope_head_dim + c.v_head_dim)),
                  (None, "heads"), rank),
        "wo": ((heads * c.v_head_dim, h), ("heads", "embed"),
               heads * c.v_head_dim),
        "ln2_w": ((h,), (None,), "ones"),
        **lanes,
    }
    if kind == "dense":
        return {**shapes, **stack.swiglu_shapes("w", h, c.intermediate_size)}
    m = c.moe_intermediate_size
    return {
        **shapes,
        "router_w": ((h, c.router_width), ("embed", None), h),
        "router_bias": ((c.router_width,), (None,), "select_bias"),
        **stack.swiglu_shapes("experts", h, m, c.n_routed_experts),
        **stack.swiglu_shapes("shared", h, c.n_shared_experts * m)}


def _top_shapes(c: LatentMoEConfig) -> Dict[str, Any]:
    h = c.hidden_size
    table = ((c.vocab_size, h), ("vocab", "embed"), h)
    behind = {"final_norm_w": ((h,), (None,), "ones")}
    if c.hc_mult is not None:
        behind["hc_head"] = stack.hc_head_shapes(h, c.hc_mult)
    top = {"tok_embed": table, "lm_head": table, **behind}
    if c.num_nextn_predict_layers:
        top["mtp"] = {"enorm_w": ((h,), (None,), "ones"),
                      "hnorm_w": ((h,), (None,), "ones"),
                      "w_eh": ((2 * h, h), (None, "embed"), 2 * h),
                      "layer": _layer_shapes("moe", c), **behind}
    return top


_PARAMS = stack.Params(
    stack.one_kind(segments), _layer_shapes, _top_shapes,
    {"select_bias": lambda key, shape: jax.random.normal(key, shape) * 0.01})
logical_axes, num_params = _PARAMS.logical_axes, _PARAMS.num_params


def init_params(config: LatentMoEConfig, key) -> Dict[str, Any]:
    """{"tok_embed", "layers": {segNN: {"0": layer parameters stacked on a
    leading repeats axis}}, "final_norm_w", "lm_head" [vocab, hidden]};
    with lanes the collapse's group "hc_head"; with the second loss the group
    "mtp": its norms, W_eh, "layer" (ONE expert layer's leaves, unstacked),
    its own final norm and collapse."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    keys = {"tok_embed": k_embed, "lm_head": k_head, "layers": k_layers}
    for name in ("hc_head", "mtp"):     # the older keys stand
        keys[name] = jax.random.fold_in(key, len(keys))
    return _PARAMS.init(config, keys)


def not_trained(config: LatentMoEConfig) -> Dict[str, Any]:
    """True at the leaves a train step leaves as they are: the router's
    selection bias."""
    return _PARAMS.tree(config, lambda name, spec: name == "router_bias")


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

_matmul = stack.matmul


def rope_tables(seq: int, c: LatentMoEConfig):
    """(cos, sin) [seq, rope / 2] float32 at positions 0 .. seq - 1; under
    yarn its frequencies, and its factor in both."""
    half = c.qk_rope_head_dim // 2
    if c.rope_scaling is None:
        inv_freq = 1.0 / (c.rope_theta ** (jnp.arange(half, dtype=F32) / half))
        angle = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
        return jnp.cos(angle), jnp.sin(angle)
    yarn = c.yarn
    angle = jnp.arange(seq, dtype=F32)[:, None] * yarn_inv_freq(
        yarn, c.qk_rope_head_dim)[None, :]
    factor = _mscale(yarn["factor"], yarn.get("mscale", 1)) / _mscale(
        yarn["factor"], yarn.get("mscale_all_dim", 0) or 1)
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def rope_interleaved(x, cos, sin):
    """x [b, s, heads, rope]: the pairs (2i, 2i + 1) turned by the angle of
    frequency i; float32 arithmetic, rounded once to x's dtype.  The
    published pairing, written down plainly: the program ropes inside the
    flash kernels (`_attention`, `_pairs_halved`), and the tests hold it
    to this."""
    pairs = x.astype(F32).reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    out = jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _pairs_halved(x, width: int, rope: int):
    """x [.., n x width], whose last `rope` columns of every `width` hold
    the rotary pairs (2i, 2i + 1): the same with those columns in the order
    [evens | odds], pair i now (i, i + rope / 2), the pairing the flash
    kernels' rope turns (`ops.attention.rope_reference`).  q_pe . k_pe does
    not see ONE reordering of both, so attention is what the published
    pairing gives.  Done where it is cheap, on W_q (25 MB a layer, where q
    is 201) and on the one rotary key, as a matmul with a constant 0 / 1
    matrix: exact in any dtype, and the gradient comes back through its
    transpose, so the parameters keep their published column order."""
    first = width - rope
    order = np.arange(width)
    order[first:] = first + np.concatenate([np.arange(0, rope, 2),
                                            np.arange(1, rope, 2)])
    reorder = np.zeros((width, width), np.float32)
    reorder[order, np.arange(width)] = 1.0
    out = jnp.einsum("...gw,wv->...gv", x.reshape(*x.shape[:-1], -1, width),
                     jnp.asarray(reorder, x.dtype),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=x.dtype)
    return out.reshape(x.shape)


def _attention(u, lp, cos, sin, c: LatentMoEConfig):
    """q, [k_nope | v] and the one rotary key go to the kernels as their
    projections lay them, un-roped (`latent_flash_attention`).  The caller's
    scope (`_layer`) names it: the kernels and W_o; the latent projections
    have their own inside it."""
    from ray_tpu.ops.attention import latent_flash_attention

    b, s, _ = u.shape
    heads, nope, rope = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim)
    rank, dv = c.kv_lora_rank, c.v_head_dim
    with jax.named_scope(common.MLA_PROJECT):
        if c.q_lora_rank is None:
            wq = _pairs_halved(lp["wq"].astype(c.dtype), nope + rope, rope)
            q = _matmul(u, wq, c).reshape(b, s, heads, nope + rope)
        else:
            c_q = rms_norm(_matmul(u, lp["wq_a"], c), lp["q_norm_w"],
                           c.rms_norm_eps)
            wq = _pairs_halved(lp["wq_b"].astype(c.dtype), nope + rope, rope)
            q = _matmul(c_q, wq, c).reshape(b, s, heads, nope + rope)
        q = with_logical_constraint(q, ("batch", "seq", "heads", None))
        latent = _matmul(u, lp["wkv_a"], c)
        c_kv = rms_norm(latent[..., :rank], lp["kv_norm_w"], c.rms_norm_eps)
        kv = _matmul(c_kv, lp["wkv_b"], c).reshape(b, s, heads, nope + dv)
        k_pe = _pairs_halved(latent[..., rank:], rope, rope)
    tables = tuple(jnp.broadcast_to(t, (b, *t.shape)) for t in (cos, sin))
    a = latent_flash_attention(q, kv, k_pe, tables,
                               sm_scale=c.softmax_scale)
    return _matmul(a.reshape(b, s, heads * dv), lp["wo"], c)


def _routed_part(flat, router_w, router_bias, w_gate, w_up, w_down,
                 c: LatentMoEConfig):
    """`stack.routed_part` behind the sigmoid router: flat [T, hidden] ->
    (the held experts' sum, the routing counts).  The usual buffer holds
    TWICE the rows even routing sends here: this cell holds an eighth of
    the experts (models/swa_moe.py's `USUAL_LOAD` has the readings that
    made a thirty-second's share 4)."""
    return stack.routed_part(
        flat, lambda: moe.sigmoid_route(
            flat, router_w, router_bias,
            num_experts_per_token=c.num_experts_per_tok,
            scale=c.routed_scaling_factor),
        w_gate, w_up, w_down, c, 2)


def routed_experts(h, router_w, router_bias, w_gate, w_up, w_down,
                   config: LatentMoEConfig):
    """The routed part of an expert layer ALONE, on its operands as the
    layer makes them (h [.., hidden]: the normed input; the router's weight
    and selection bias; the held experts' weights) -> the held experts' sum,
    like h: the router, the dropless dispatch and the grouped matmuls, no
    shared expert."""
    y, _ = _routed_part(h.reshape(-1, h.shape[-1]), router_w, router_bias,
                        w_gate, w_up, w_down, config)
    return y.reshape(h.shape)


def _layer(x, lp, tables, *, kind: str, c: LatentMoEConfig):
    """One layer, tables its (cos, sin) -> (x, the routing counts of an
    expert layer or None).  Each sublayer goes round the stream by
    `stack.residual`: x + f(norm(x)), or the lanes' two calls."""
    def attention(x):
        with jax.named_scope(common.ATTN_FULL):
            u = rms_norm(x, lp["ln1_w"], c.rms_norm_eps)
            u = with_logical_constraint(u, ("batch", "seq", "embed"))
            return _attention(u, lp, *tables, c)

    stats = None

    def feed_forward(x):
        nonlocal stats
        with jax.named_scope(common.MLP):
            y = rms_norm(x, lp["ln2_w"], c.rms_norm_eps)
        if kind == "dense":
            return common.swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"],
                                 c.dtype)
        routed, stats = _routed_part(
            y.reshape(-1, y.shape[-1]), lp["router_w"], lp["router_bias"],
            lp["experts_gate"], lp["experts_up"], lp["experts_down"], c)
        return routed.reshape(y.shape) + common.swiglu(
            y, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
            c.dtype)

    if c.hc_mult and c.remat and c.remat_policy == "full":
        # where the layer's checkpoint keeps nothing, a stream of lanes
        # makes attention's arrays again at attention's OWN backward: the
        # feed-forward's backward comes first and holds three copies of the
        # stream beside the routed buffers, and attention's 0.4 GiB (one
        # row of 8192 tokens) waiting beside them are what does not fit.
        # Of those the flash kernel's out and lse (65 MiB) do fit, and are
        # kept, in ONE layer at a time: from the layer's forward made again
        # inside its backward to its attention's backward, which then makes
        # the projections a third time (q, kv and the rotary key, 0.33 GiB)
        # and runs no flash forward
        attention = common.maybe_remat(attention, True, "save_attn")
        dispatch.record("latent_moe.attention_checkpoint",
                        "kept:" + ",".join(common.SAVE_ATTN_NAMES))
    mixes = [] if kind == "moe" and c.hc_mult else None
    x = stack.residual(x, attention, lp, "hc_attn", c, mixes)
    x = stack.residual(x, feed_forward, lp, "hc_ffn", c, mixes)
    if mixes:
        with jax.named_scope(common.RESID_MIX):
            stats = {**stats, "hc_row_err": stack.comb_row_err(mixes, c)}
    return x, stats


def residual_mix(x, w_hc, scale, base, config: LatentMoEConfig):
    """The lanes' mixing ALONE, round the identity, on its operands as a
    sublayer has them (x [b, s, n hidden]: the stream; the sublayer's three
    lane leaves) -> x' = post u + comb x with u = sum pre x: both calls of
    ops/hyper_connection.py and their product, no sublayer between.  u and
    x' leave in FLOAT32, the passes' own numbers: rounded to the stream's
    dtype, as the model's cross HBM, a rounding of comb's size (the Sinkhorn
    rounds made in bfloat16) is lost in the result's own."""
    from ray_tpu.ops.hyper_connection import hc_post, hc_pre

    hc, x = stack.hyper(config), x.astype(config.dtype)
    u, mix, x = hc_pre(x, w_hc, scale, base, hc, out_dtype=F32)
    return hc_post(x, u, mix, hc, out_dtype=F32)


def _into_lanes(x, c: LatentMoEConfig):
    """[b, s, hidden] -> the stream: every lane a copy, side by side (a
    tile's [.., n, hidden] view would lie in tiles of 16 rows, 12 of them
    padding)."""
    return x if c.hc_mult is None \
        else jnp.concatenate([x] * c.hc_mult, axis=-1)


def _collapsed(x, leaves, c: LatentMoEConfig):
    """The stream behind its last layer -> [b, s, hidden]: the lanes'
    per-token weighted sum by the group `leaves["hc_head"]`."""
    if c.hc_mult is None:
        return x
    from ray_tpu.ops.hyper_connection import hc_collapse

    with jax.named_scope(common.RESID_MIX):
        head = leaves["hc_head"]
        return hc_collapse(x, head["w"], head["scale"], head["base"],
                           stack.hyper(c))


def _tables(seq: int, c: LatentMoEConfig):
    with jax.named_scope(common.ATTN_FULL):     # the tables are attention's
        return rope_tables(seq, c)


def _stream(params: Dict[str, Any], tokens, config: LatentMoEConfig):
    """Embedding + layers: [b, s] -> (the stack's output BEFORE the final
    norm [b, s, hidden], the lanes collapsed; the routing counts)."""
    c, segs = config, segments(config)
    tables = _tables(tokens.shape[1], c)

    def leading(table, layers):
        """The embedding, as lanes, through the dense layers."""
        x = _into_lanes(common.embed_tokens(table, tokens, c.dtype), c)
        return stack.walk(_layer, c, segs[:1], layers, x,
                          lambda kind: tables)[0]

    if c.hc_mult and c.remat and c.remat_policy == "full":
        # the lanes' n copies of the embedding are the widest thing a
        # layer's checkpoint keeps and the cheapest to make again: under
        # one more checkpoint they exist at the dense layers' backward
        # alone, not through the expert layers'
        leading = jax.checkpoint(leading)
    x, stats = stack.walk(
        _layer, c, segs[1:], params["layers"],
        leading(params["tok_embed"], params["layers"]), lambda kind: tables,
        first=1)
    return _collapsed(x, params, c), stats


def _final_norm(leaves, x, config: LatentMoEConfig):
    with jax.named_scope(common.LOSS):
        return rms_norm(x, leaves["final_norm_w"], config.rms_norm_eps)


def forward_hidden(params: Dict[str, Any], tokens, config: LatentMoEConfig):
    """Embedding + layers + final RMSNorm: [b, s] -> ([b, s, hidden], the
    LAST expert layer's routing counts and the rows all the expert layers
    held together, or None without one)."""
    x, stats = _stream(params, tokens, config)
    return _final_norm(params, x, config), stats


def _mtp_hidden(params: Dict[str, Any], x, next_tokens,
                config: LatentMoEConfig):
    """The multi-token-prediction block: x [b, s, hidden] the stream before
    the final norm, next_tokens [b, s] the tokens one position on -> its
    normed output [b, s, hidden], which the SHARED head scores against the
    tokens two positions on.  Its expert layer is `_layer` under the
    config's remat, as the stack's are."""
    c, mp = config, params["mtp"]
    e = common.embed_tokens(params["tok_embed"], next_tokens, c.dtype)
    with jax.named_scope(common.EMBED):
        both = jnp.concatenate(
            [rms_norm(e, mp["enorm_w"], c.rms_norm_eps),
             rms_norm(x, mp["hnorm_w"], c.rms_norm_eps)], axis=-1)
        h = _matmul(both, mp["w_eh"], c)
    lanes, _ = stack.layer_fn(_layer, "moe", c)(
        _into_lanes(h, c), mp["layer"], _tables(x.shape[1], c))
    return _final_norm(mp, _collapsed(lanes, mp, c), c)


def _with_second(params: Dict[str, Any], tokens, next_tokens,
                 config: LatentMoEConfig):
    """`stack.LossTail`'s `second`: None without the block, else (what
    `forward_hidden` gives, the block's normed output, the second loss's
    weight)."""
    if not config.num_nextn_predict_layers:
        return None
    x, stats = _stream(params, tokens, config)
    return ((_final_norm(params, x, config), stats),
            _mtp_hidden(params, x, next_tokens, config), MTP_LOSS_WEIGHT)


_TAIL = stack.LossTail(forward_hidden, head="lm_head", second=_with_second)
forward, token_nll, loss_fn = _TAIL.forward, _TAIL.token_nll, _TAIL.loss_fn


def loss_and_metrics(params: Dict[str, Any], batch, config: LatentMoEConfig):
    """`stack.LossTail.loss_and_metrics`; the lanes' counter, which rides
    the scan beside the last expert layer's routing counts, under its own
    name `hc_row_err`."""
    loss, metrics = _TAIL.loss_and_metrics(params, batch, config)
    if "moe_hc_row_err" in metrics:
        metrics["hc_row_err"] = metrics.pop("moe_hc_row_err")
    return loss, metrics
