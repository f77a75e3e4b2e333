"""Compressed convolutional attention beside a top-1 expert layer whose
router is an MLP that carries its state from layer to layer (the `zaya`
form, as ZAYA1-8B publishes it) for training through `ShardedTrainStep`, on
models/stack.py's layer stack, with the probe `cca_mix`; embedding,
cross-entropy, the depthwise convolution and the tiles are
models/common.py's, the selection and the routed experts models/moe.py's
dropless layer, attention ops/attention.py's flash kernels.

Layer equations (x the layer's input [s, E]; every matrix [in, out]; H_q
query heads over H_kv KV heads of d columns, group G = H_q / H_kv; the
latents L_q = H_q d and L_kv = H_kv d; every norm a PLAIN RMSNorm, x /
rms(x) w, eps `rms_norm_eps`):

  residual   a sublayer f with pre-norm n updates the stream as x <- (s_r x
             + b_r) + (s_h f(n(x)) + b_h): four learned vectors of E a
             sublayer.
  attention  h = n1(x).  q~ = h W_q [s, L_q], k~ = h W_k [s, L_kv], no bias.
             v = [h W_v1 | shift(h) W_v2], shift(h)_t = h_(t-1) and 0 at
             t = 0, each half L_kv / 2 wide: the first half of the KV heads
             carries the current token's values, the second the previous
             token's.  Two causal convolutions over c = [q~ | k~]: c1_t =
             sum_j a_j c_(t - (taps0 - 1) + j) + b_1, depthwise (`cca_time0`
             taps); c2_t = sum_j c1_(t - (taps1 - 1) + j) B_j^(head) + b_2
             (`cca_time1` taps), B a [d, d] matrix a tap for each of the H_q
             + H_kv heads: channels mix inside a head, never across heads;
             zero history before position 0.  The q-k mean, from q~ and k~
             BEFORE the convolutions: M_q = (Q~ + K~ repeated over its
             group) / 2, M_k[g] = the mean of M_q over group g; Q = heads(
             c2[:, :L_q]) + M_q, K = heads(c2[:, L_q:]) + M_k.  l2 norm and
             temperature over a head's columns: Q <- Q / sqrt(sum Q^2 +
             1e-6) sqrt(d), K likewise x tau_g, one learned scalar a KV
             head.  Rope on the FIRST d x `partial_rotary_factor` columns of
             Q and K, half-split pairing; causal softmax attention at 1 /
             sqrt(d), query head j reading KV head j // G; y = o W_o, W_o
             [L_q, E].
  experts    h = n2(x).  The router, all of it float32 at full matmul
             precision: r = h W_rd + b_rd [s, R]; r <- r + alpha r_prev
             (r_prev the previous layer's r after its own such step, zero
             for the first layer; the gradient flows through it); r goes on
             to the next layer; z = W_3 gelu(W_2 gelu(W_1 n_r(r) + b_1) +
             b_2) (the exact gelu; W_3 [R, `router_width`] without bias); p
             = softmax(z); a token's `num_experts_per_tok` experts are the
             top of p + beta (beta enters the selection only, gets no
             gradient and is not trained); its gates p[sel] AS THEY ARE.
             f = sum over the chosen experts HELD HERE of p[sel] SwiGLU_e(
             h).  No shared expert, no auxiliary loss.
  model      tied embedding (no input scale), the layers with (x, r) as
             the carry, a final norm, logits x W_emb^T.

One chip's share (`stack.routed_part`): `num_experts` experts held HERE,
from `first_held_expert` on, of `router_width` routed over.  Every layer is
of one kind, so there is one segment.

Precision.  The matmuls take their operands in the dtype the WEIGHTS come
in and accumulate in float32: the layer hands `_mix` its matrices in the
compute dtype (bfloat16: one exact MXU pass), the probe `cca_mix` hands it
the reference's float32 ones (full matmul precision).  Everything between
the projections and the kernels (`attn.mix`: the shift, both convolutions'
sums, the mean, the l2 norm) is float32 either way and is rounded once, to
the compute dtype, where the kernels take Q, K and V.

How the half rope reaches the kernels, whose rope turns the WHOLE head: Q
and K are no projection's output here, so it is their columns, not a
weight's, that are reordered once where the kernels take them
(`stack.rotary_first`), with tables that have an identity tail
(`stack.kernel_tables`; `dispatch.taken()["cca_moe.rope"]`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common, moe, stack
from ray_tpu.models.swa_moe import USUAL_LOAD, _frozen
from ray_tpu.models.transformer import rms_norm
from ray_tpu.ops import dispatch
from ray_tpu.parallel.sharding import with_logical_constraint

F32 = jnp.float32
HYBRID = "hybrid"       # the one kind of layer: attention + experts
L2_EPS = 1e-6
PUBLISHED_ROPE = {
    HYBRID: {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
             "rope_type": "default"},
    "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                       "rope_type": "default"},
    "rope_type": "default",
}


@dataclasses.dataclass(frozen=True)
class CcaMoEConfig:
    """The published config.json's key names, the chip's share
    (`router_width`, `first_held_expert`) and the train switches the other
    models have.  `layer_types` may be given whole: a program of
    `num_hidden_layers` layers runs its first that many."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = (HYBRID,) * 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_parameters: Any = None         # None: the published group
    sliding_window: Optional[int] = None
    attention_bias: bool = False
    lm_head_bias: bool = False
    hidden_act: str = "silu"
    num_experts: int = 16               # held HERE
    router_width: Optional[int] = None  # routed over; None: all are held
    first_held_expert: int = 0
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
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
        put("rope_parameters", _frozen(self.rope_parameters or PUBLISHED_ROPE))
        rope = dict(self.rope_parameters).get(HYBRID)
        unsupported = {
            "layer_types": set(self.layer_types) != {HYBRID},
            "rope_parameters": rope is None
            or dict(rope).get("rope_type", "default") != "default"
            or dict(rope).get("partial_rotary_factor")
            != self.partial_rotary_factor,
            "sliding_window": self.sliding_window is not None,
            "attention_bias": self.attention_bias,
            "lm_head_bias": self.lm_head_bias,
            "hidden_act": self.hidden_act != "silu",
            "tie_word_embeddings": not self.tie_word_embeddings,
            "cca_time": min(self.cca_time0, self.cca_time1) < 1,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(f"not written down here, so not computed: {bad}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError("query heads must be a multiple of the KV "
                             "heads, and the KV heads halve into current "
                             "and shifted values")
        if self.first_held_expert + self.num_experts > self.router_width:
            raise ValueError("the held experts lie outside the router's")
        if 2 * self.rotary_width != self.head_dim or self.head_dim % 4:
            raise ValueError("the partial rope reaches the kernels by "
                             f"halves: it turns {self.rotary_width} of "
                             f"{self.head_dim}")

    @property
    def rope_theta(self) -> float:
        return float(dict(dict(self.rope_parameters)[HYBRID])["rope_theta"])

    @property
    def rotary_width(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def experts_held(self) -> Tuple[int, int]:
        return self.first_held_expert, self.num_experts

    @property
    def latents(self) -> Tuple[int, int]:
        """(L_q, L_kv): the widths attention runs in."""
        return (self.num_attention_heads * self.head_dim,
                self.num_key_value_heads * self.head_dim)

    @classmethod
    def tiny(cls, **kw) -> "CcaMoEConfig":
        """Test-sized: four layers, 4 query / 2 KV heads of 64 (two a lane
        block, so that the flash kernels pad nothing), latents of 256 and
        128 under a hidden 96, a router of 32, 4 of 16 experts held."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=96, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=64,
            num_experts=4, router_width=16, moe_intermediate_size=32,
            router_hidden_size=32), **kw})


def segments(config: CcaMoEConfig) -> List[Tuple[str, int, int]]:
    """(kind, first layer, repeats): every layer is of the one kind."""
    return [(HYBRID, 0, config.num_hidden_layers)]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
# Every vector is DRAWN, not a constant: a program that leaves a residual
# scale, a conv's bias, tau or the carry's alpha out, or reads a plain norm
# as a zero-centred one, then differs from the reference on every seed.

def _layer_shapes(c: CcaMoEConfig) -> Dict[str, Tuple]:
    """name -> (shape, logical axes, init): init is a matrix's fan-in, or
    how a vector is drawn ("near_one", "small", "residual_bias",
    "select_bias")."""
    h, d = c.hidden_size, c.head_dim
    heads, kv = c.num_attention_heads, c.num_key_value_heads
    lq, lkv = c.latents
    r, width = c.router_hidden_size, c.router_width

    def residual(prefix):
        return {f"{prefix}_sr": ((h,), (None,), "near_one"),
                f"{prefix}_br": ((h,), (None,), "residual_bias"),
                f"{prefix}_sh": ((h,), (None,), "near_one"),
                f"{prefix}_bh": ((h,), (None,), "residual_bias")}

    return {
        **residual("attn"),
        "ln1_w": ((h,), (None,), "near_one"),
        "wq": ((h, lq), ("embed", "heads"), h),
        "wk": ((h, lkv), ("embed", "kv"), h),
        "wv1": ((h, lkv // 2), ("embed", "kv"), h),
        "wv2": ((h, lkv // 2), ("embed", "kv"), h),
        "conv0_w": ((c.cca_time0, lq + lkv), (None, "heads"), c.cca_time0),
        "conv0_b": ((lq + lkv,), ("heads",), "small"),
        "conv1_w": ((heads + kv, c.cca_time1, d, d),
                    (None, None, None, None), c.cca_time1 * d),
        "conv1_b": ((lq + lkv,), ("heads",), "small"),
        "tau": ((kv,), (None,), "near_one"),
        "wo": ((lq, h), ("heads", "embed"), lq),
        **residual("ffn"),
        "ln2_w": ((h,), (None,), "near_one"),
        "router_down_w": ((h, r), ("embed", None), h),
        "router_down_b": ((r,), (None,), "small"),
        "router_carry": ((r,), (None,), "near_one"),
        "router_norm_w": ((r,), (None,), "near_one"),
        "router_w1": ((r, r), (None, None), r),
        "router_b1": ((r,), (None,), "small"),
        "router_w2": ((r, r), (None, None), r),
        "router_b2": ((r,), (None,), "small"),
        "router_w3": ((r, width), (None, None), r),
        "router_bias": ((width,), (None,), "select_bias"),
        **stack.swiglu_shapes("experts", h, c.moe_intermediate_size,
                              c.num_experts),
    }


def _normal_times(scale: float):
    return lambda key, shape: scale * jax.random.normal(key, shape)


def _top_shapes(c: CcaMoEConfig) -> Dict[str, Tuple]:
    return {"tok_embed": ((c.vocab_size, c.hidden_size), ("vocab", "embed"),
                          c.hidden_size),
            "final_norm_w": ((c.hidden_size,), (None,), "near_one")}


_PARAMS = stack.Params(
    stack.one_kind(segments), lambda kind, c: _layer_shapes(c),
    _top_shapes, {
        "near_one": lambda key, shape: (
            1.0 + 0.1 * jax.random.normal(key, shape)),
        "small": _normal_times(0.1), "residual_bias": _normal_times(0.01),
        "select_bias": _normal_times(0.01),
        "fan_in": lambda key, shape, fan_in: (
            jax.random.normal(key, shape) / math.sqrt(fan_in))})
logical_axes, num_params = _PARAMS.logical_axes, _PARAMS.num_params


def init_params(config: CcaMoEConfig, key) -> Dict[str, Any]:
    """{"tok_embed" (the head too), "layers": {"seg00": {"0": layer
    parameters stacked on a leading repeats axis}}, "final_norm_w"}."""
    k_embed, k_norm, k_layers = jax.random.split(key, 3)
    return _PARAMS.init(config, {"tok_embed": k_embed, "final_norm_w": k_norm,
                                 "layers": k_layers})


def not_trained(config: CcaMoEConfig) -> Dict[str, Any]:
    """True at the leaves a train step leaves as they are: the router's
    selection bias (its update rule is a trainer's, not the layer's)."""
    return _PARAMS.tree(config, lambda name, spec: name == "router_bias")


# ---------------------------------------------------------------------------
# The mixer
# ---------------------------------------------------------------------------

def _matmul(x, w):
    """x w in float32, the operands in the dtype the WEIGHT comes in: the
    compute dtype's one exact MXU pass, or a float32 weight's full
    precision."""
    precision = jax.lax.Precision.HIGHEST if w.dtype == F32 else None
    return jnp.einsum("bsi,io->bso", x.astype(w.dtype), w,
                      precision=precision, preferred_element_type=F32)


def _shifted(x, back: int):
    """x [b, s, w] -> its rows `back` positions later, zeros before them."""
    if back == 0:
        return x
    return jnp.pad(x[:, :x.shape[1] - back], ((0, 0), (back, 0), (0, 0)))


_per_head = stack.per_head


def _two_convs(x, heads: int, w0, b0, w1, b1):
    """x [b, s, heads x d] float32 -> the depthwise convolution (w0 [taps0,
    heads x d], b0), then the one that mixes a head's d channels over its
    taps (w1 [heads, taps1, d, d], b1): both causal, zero history.  A head
    is a whole-tile slice of columns at d = 128, and its taps are plain
    matmuls."""
    c1 = common.causal_depthwise_conv(x, w0.astype(F32), b0.astype(F32))
    taps, d = w1.shape[1], w1.shape[-1]
    history = [_shifted(c1, taps - 1 - j) for j in range(taps)]
    c2 = jnp.concatenate([
        sum(_matmul(tap[..., h * d:(h + 1) * d], w1[h, j])
            for j, tap in enumerate(history))
        for h in range(heads)], axis=-1)
    return c2 + b1.astype(F32)


def _values(u, wv1, wv2):
    """[h W_v1 | shift(h) W_v2]: (h_(t-1)) W_v2 is row t - 1 of h W_v2, so
    the narrow side is the one shifted."""
    return jnp.concatenate([_matmul(u, wv1), _shifted(_matmul(u, wv2), 1)],
                           axis=-1)


def _qk_means(q0, k0, heads: int, kv: int):
    """The q-k mean of the projections BEFORE the convolutions: (M_q = (Q~
    + K~ repeated over its group) / 2 [b, s, L_q], M_k = M_q's mean over a
    group's query heads [b, s, L_kv])."""
    group = heads // kv
    mean_q = 0.5 * (q0 + common.repeat_heads(k0, kv, group))
    return mean_q, _per_head(mean_q, heads, lambda t: jnp.mean(
        t.reshape(*t.shape[:2], kv, group, *t.shape[3:]), axis=3))


def _l2_normalised(x, heads: int, d: int, tau=None):
    """x / sqrt(sum x^2 + 1e-6) sqrt(d) over a head's d columns, times tau
    [heads] where given."""
    def l2(t):
        t = t * (math.sqrt(d) * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS))
        return t if tau is None else t * tau.astype(F32)[:, None, None]

    return _per_head(x, heads, l2)


def _mix(u, wq, wk, wv1, wv2, conv0_w, conv0_b, conv1_w, conv1_b, tau,
         c: CcaMoEConfig):
    """u [b, s, E], the normed input -> (Q [b, s, L_q], K, V [b, s, L_kv])
    float32, a head's columns in the PUBLISHED order, l2-normalised, before
    rope: the header's attention equations up to the kernels."""
    heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    lq, _ = c.latents
    q0 = with_logical_constraint(_matmul(u, wq), ("batch", "seq", "heads"))
    k0 = _matmul(u, wk)
    with jax.named_scope(common.ATTN_MIX):
        v = _values(u, wv1, wv2)
        q = _two_convs(q0, heads, conv0_w[:, :lq], conv0_b[:lq],
                       conv1_w[:heads], conv1_b[:lq])
        k = _two_convs(k0, kv, conv0_w[:, lq:], conv0_b[lq:],
                       conv1_w[heads:], conv1_b[lq:])
        mean_q, mean_k = _qk_means(q0, k0, heads, kv)
        q = _l2_normalised(q + mean_q, heads, d)
        k = _l2_normalised(k + mean_k, kv, d, tau)
    return q, k, v


def cca_mix(u, wq, wk, wv1, wv2, conv0_w, conv0_b, conv1_w, conv1_b, tau,
            config: CcaMoEConfig):
    """What compressed convolutional attention hands its kernels, ALONE, on
    its operands as the layer makes them (u [b, s, E]: the normed input;
    one layer's weights) -> [Q | K | V] [b, s, L_q + 2 L_kv] float32, a
    head's columns in the published order, before rope.  The matmuls run
    in the dtype the weights come in (the layer's are the compute dtype's)."""
    return jnp.concatenate(_mix(u, wq, wk, wv1, wv2, conv0_w, conv0_b,
                                conv1_w, conv1_b, tau, config), axis=-1)


def _rotary_first_halves(x, heads: int, c: CcaMoEConfig):
    """The last axis, heads x d with a head's columns as published -> as
    the flash kernels' rope pairs them (`stack.rotary_first`)."""
    return stack.rotary_first(x, heads, c.rotary_width)


def kernel_tables(seq: int, c: CcaMoEConfig):
    """(cos, sin) [seq, head_dim / 2] float32 as the flash kernels take
    them for a head ordered by `_rotary_first_halves`
    (`stack.kernel_tables`)."""
    return stack.kernel_tables(
        *stack.rope_tables(seq, c.rotary_width, c.rope_theta), c.head_dim)


def _attention(u, lp, tables, c: CcaMoEConfig):
    """u [b, s, E], the normed input -> the mixer's output.  The caller's
    scope (`_layer`) names it: projections, the mix, kernels and W_o."""
    from ray_tpu.ops.attention import flash_attention

    b, s, _ = u.shape
    heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q, k, v = _mix(u, *(lp[n].astype(c.dtype) for n in (
        "wq", "wk", "wv1", "wv2")), lp["conv0_w"], lp["conv0_b"],
        lp["conv1_w"].astype(c.dtype), lp["conv1_b"], lp["tau"], c)
    q = _rotary_first_halves(q, heads, c).astype(c.dtype)
    k = _rotary_first_halves(k, kv, c).astype(c.dtype)
    # the kernels take expanded heads, query head j reading KV head
    # j // group, as the projections lay them (`common.repeat_heads`)
    k, v = (common.repeat_heads(x, kv, heads // kv).reshape(b, s, heads, d)
            for x in (k, v.astype(c.dtype)))
    rope = tuple(jnp.broadcast_to(t, (b, *t.shape)) for t in tables)
    a = flash_attention(q.reshape(b, s, heads, d), k, v, causal=True,
                        sm_scale=1.0 / math.sqrt(d), rope=rope)
    return _matmul(a.reshape(b, s, heads * d),
                   lp["wo"].astype(c.dtype)).astype(c.dtype)


# ---------------------------------------------------------------------------
# The expert sublayer
# ---------------------------------------------------------------------------

def route(h, r_prev, lp, c: CcaMoEConfig):
    """The router, float32 at full matmul precision: h [T, E], the normed
    input; r_prev [T, R], the previous layer's state (zeros for the first)
    -> (expert index [T, k] int32, gates [T, k] float32, r [T, R] for the
    next layer)."""
    def dot(x, w):
        return jnp.dot(x, lp[w].astype(F32),
                       precision=jax.lax.Precision.HIGHEST)

    def vec(name):
        return lp[name].astype(F32)

    r = dot(h.astype(F32), "router_down_w") + vec("router_down_b")
    r = r + vec("router_carry") * r_prev
    n = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True)
                          + c.rms_norm_eps) * vec("router_norm_w")
    a = jax.nn.gelu(dot(n, "router_w1") + vec("router_b1"),
                    approximate=False)
    a = jax.nn.gelu(dot(a, "router_w2") + vec("router_b2"),
                    approximate=False)
    probs = jax.nn.softmax(dot(a, "router_w3"), axis=-1)
    idx, gates = moe.select_experts(
        probs, lp["router_bias"],
        num_experts_per_token=c.num_experts_per_tok, gate_rule="raw")
    return idx, gates, r


def _routed_part(flat, r_prev, lp, c: CcaMoEConfig):
    """`stack.routed_part` behind `route`: flat [T, E] -> (the held
    experts' sum, the router's state, the routing counts).  `USUAL_LOAD`
    matters to a share alone: with every expert held, as in the cell, the
    bound is the step's tokens."""
    y, stats, r = stack.routed_part(
        flat, lambda: route(flat, r_prev, lp, c), lp["experts_gate"],
        lp["experts_up"], lp["experts_down"], c, USUAL_LOAD)
    return y, r, stats


def _residual(x, f, lp, prefix: str):
    """(s_r x + b_r) + (s_h f + b_h) in float32, in x's dtype."""
    s_r, b_r, s_h, b_h = (lp[f"{prefix}_{n}"].astype(F32)
                          for n in ("sr", "br", "sh", "bh"))
    return ((s_r * x.astype(F32) + b_r)
            + (s_h * f.astype(F32) + b_h)).astype(x.dtype)


def _layer(carry, lp, tables, *, kind: str, c: CcaMoEConfig):
    """One layer: (x, the router's state) -> ((x, the router's state), the
    expert layer's routing counts).  The residual scaling lies under its
    sublayer's scope: `attn.full`, and `mlp` beside the experts' pre-norm."""
    del kind                                    # there is the one
    x, r_prev = carry
    with jax.named_scope(common.ATTN_FULL):
        u = rms_norm(x, lp["ln1_w"], c.rms_norm_eps)
        u = with_logical_constraint(u, ("batch", "seq", "embed"))
        x = _residual(x, _attention(u, lp, tables, c), lp, "attn")
    x = with_logical_constraint(x, ("batch", "seq", "embed"))
    with jax.named_scope(common.MLP):
        y = rms_norm(x, lp["ln2_w"], c.rms_norm_eps)
    routed, r, stats = _routed_part(y.reshape(-1, y.shape[-1]), r_prev, lp, c)
    with jax.named_scope(common.MLP):
        x = _residual(x, routed.reshape(y.shape), lp, "ffn")
    return (with_logical_constraint(x, ("batch", "seq", "embed")), r), stats


def forward_hidden(params: Dict[str, Any], tokens, config: CcaMoEConfig):
    """Embedding + layers + the final norm: [b, s] -> ([b, s, E], the LAST
    layer's routing counts and the rows all the layers held together)."""
    c = config
    b, s = tokens.shape
    x = common.embed_tokens(params["tok_embed"], tokens, c.dtype)
    with jax.named_scope(common.ATTN_FULL):     # the tables are attention's
        tables = kernel_tables(s, c)
    heads, kv = c.num_attention_heads, c.num_key_value_heads
    dispatch.record("cca_moe.mix", (
        f"taps{c.cca_time0}+{c.cca_time1},heads{heads}over{kv},"
        f"latent{c.latents[0]}+{c.latents[1]},vshift,l2tau,xla"))
    dispatch.record("cca_moe.rope",
                    stack.rope_word(HYBRID, c.rotary_width, c.head_dim))
    (x, _), stats = stack.walk(
        _layer, c, segments(c), params["layers"],
        (x, jnp.zeros((b * s, c.router_hidden_size), F32)),
        lambda kind: tables)
    with jax.named_scope(common.LOSS):
        return rms_norm(x, params["final_norm_w"], c.rms_norm_eps), stats


_TAIL = stack.LossTail(forward_hidden, head="tok_embed")
token_nll, loss_and_metrics = _TAIL.token_nll, _TAIL.loss_and_metrics
loss_fn = _TAIL.loss_fn
