"""Mamba-2 (state-space-dual) mixers, un-roped GQA attention and sigmoid-
routed UNGATED relu^2 experts in layers that are ONE sublayer each, in the
order a pattern string gives (the `nemotron_h` form, as NVIDIA-Nemotron-3-
Nano-30B-A3B publishes it) for training through `ShardedTrainStep`, on
models/stack.py's layer stack; embedding and cross-entropy are
models/common.py's, the routed experts models/moe.py's dropless layer, the
recurrence ops/ssd_scan.py's kernels, the chain round it (the convolution
with its bias, SiLU and the cut into x, B, C in front; the gate and the norm
by groups behind) ops/mixer_chain.py's two passes, attention
ops/attention.py's flash kernels.

Layer equations (x the layer's input [s, hidden]; every matrix [in, out];
every norm a plain RMSNorm, x / rms(x) w, eps `layer_norm_epsilon`, sums in
float32; E = hidden):

  block      layer l of kind pattern[l]: x = x + f_l(n_l(x)), ONE sublayer
             a layer; a final norm; logits through an UNTIED head.
  M, mamba   u the normed input; H = `mamba_num_heads` heads of P =
             `mamba_head_dim`, d_inner = H P (NOT `expand` x hidden), G =
             `n_groups` groups of N = `ssm_state_size`.  [z | xBC | dt~] =
             u W_in (widths d_inner, d_inner + 2 G N, H; no bias); xBC <-
             silu(causal depthwise conv over `conv_kernel` taps WITH bias);
             x = xBC[:d_inner] as [H, P], B = the next G N as [G, N], C the
             last; head h reads group h // (H / G).  dt = softplus(dt~ +
             dt_bias), float32, unclamped; a = -exp(A_log), a scalar a
             head.  A head, float32, S [P, N] from zero: S <- exp(dt a) S +
             dt x (x) B; y = S C + D x.  y <- rmsnorm_g(y silu(z)) w: the
             gate BEFORE the norm, the mean square over each of G groups of
             d_inner / G channels apart; then W_out.
  *, full    q = u W_q over `num_attention_heads`, k = u W_k, v = u W_v
             over `num_key_value_heads` of `head_dim`; no bias, NO
             positional encoding (the mixers carry position), no norm, no
             gate; causal softmax at 1 / sqrt(head_dim), query head j
             reading KV head j // group; W_o.
  E, experts s = sigmoid(u W_r) in float32 over `router_width` experts; the
             top `num_experts_per_tok` of s + b (b enters the selection
             only and is not trained); gates s[sel] / (sum s[sel]) x
             `routed_scaling_factor`; sum over the chosen experts HELD HERE
             of gate_e relu(u W_up_e)^2 W_down_e + relu(u W_su)^2 W_sd: two
             matrices an expert, no gate matrix, the shared expert added
             ungated.  No auxiliary loss.

One chip's share (`stack.routed_part`): `n_routed_experts` experts held
HERE, from `first_held_expert` on, of `router_width` routed over.  The
segments are maximal runs of layers of one kind; the published pattern
alternates, so nearly every segment is one layer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common, moe, stack
from ray_tpu.models.hybrid import _dt_bias
from ray_tpu.models.swa_moe import USUAL_LOAD
from ray_tpu.ops import dispatch
from ray_tpu.ops.mixer_chain import conv_silu_split, gated_group_norm
from ray_tpu.parallel.sharding import with_logical_constraint

F32 = jnp.float32
MAMBA, FULL, EXPERTS = "M", "*", "E"


@dataclasses.dataclass(frozen=True)
class SsdMoEConfig:
    """The published config.json's key names, the chip's share
    (`router_width`, `first_held_expert`) and the train switches the other
    models have."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2                     # multiplies nothing: the heads decide
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    n_routed_experts: int = 128         # held HERE
    router_width: Optional[int] = None  # routed over; None: all are held
    first_held_expert: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    residual_in_fp32: bool = False
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    fused_ce: bool = False

    def __post_init__(self):
        if self.router_width is None:
            object.__setattr__(self, "router_width", self.n_routed_experts)
        unsupported = {
            "use_conv_bias": not self.use_conv_bias,
            "mamba_proj_bias": self.mamba_proj_bias,
            "mamba_hidden_act": self.mamba_hidden_act != "silu",
            "attention_bias": self.attention_bias,
            "n_shared_experts": self.n_shared_experts != 1,
            "mlp_hidden_act": self.mlp_hidden_act != "relu2",
            "mlp_bias": self.mlp_bias,
            "norm_topk_prob": not self.norm_topk_prob,
            "n_group": self.n_group != 1 or self.topk_group != 1,
            "residual_in_fp32": self.residual_in_fp32,
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(f"not written down here, so not computed: {bad}")
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers \
                or set(pattern) - {MAMBA, FULL, EXPERTS}:
            raise ValueError(f"{self.num_hidden_layers} layers, the pattern "
                             f"{pattern!r}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.mamba_num_heads % self.n_groups \
                or self.d_inner % self.n_groups:
            raise ValueError("heads must be a multiple of the KV heads, of "
                             "the groups")
        if self.first_held_expert + self.n_routed_experts > self.router_width:
            raise ValueError("the held experts lie outside the router's")

    @property
    def experts_held(self) -> Tuple[int, int]:
        return self.first_held_expert, self.n_routed_experts

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(self.hybrid_override_pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @classmethod
    def tiny(cls, **kw) -> "SsdMoEConfig":
        """Test-sized: every kind of layer, 4 heads of 64 over 2 groups with
        a 128-wide state (the kernels' shapes), GQA at a group of 2, 4 of
        16 experts held, an expert width that is half a lane tile over a
        whole one, and `expand` x hidden that is NOT heads x head size."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=5,
            hybrid_override_pattern="MEM*E", mamba_num_heads=4,
            mamba_head_dim=64, n_groups=2, ssm_state_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=64,
            n_routed_experts=4, router_width=16, num_experts_per_tok=3,
            moe_intermediate_size=192,
            moe_shared_expert_intermediate_size=64), **kw})


def segments(config: SsdMoEConfig) -> List[Tuple[str, int, int]]:
    """(kind, first layer, repeats): maximal runs of layers of one kind."""
    return stack.runs(config.layer_kinds)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
# Norm weights, the conv's bias, the selection bias, A_log and dt_bias are
# DRAWN, not constants: a program that drops one then differs from the
# reference on every seed.  A and the step follow the family's published
# initialisation: A uniform in [1, 16], dt_bias the inverse softplus of a
# step drawn log-uniformly in [0.001, 0.1]; D ones.  The matrices that
# bring a sublayer's output back to the stream (W_out, W_o, W_down, the
# shared expert's) are drawn CENTRED over their fan-in ("fan_in_centred"):
# what they read has a positive mean (relu^2, SiLU's gate), and an
# uncentred draw turns that mean into ONE vector added to every token,
# a third of the normed stream by the third layer, which tilts the router's
# 128 scores the same way for every token; a trained model's selection bias
# levels its experts' load, a drawn one does not, and the held experts'
# rows then move 15 % with the seed (PERF.md, PR 48).

def _layer_shapes(kind: str, c: SsdMoEConfig) -> Dict[str, Tuple]:
    """name -> (shape, logical axes, init): init is a matrix's fan-in, or
    how a vector is drawn."""
    h = c.hidden_size
    norm = {"ln_w": ((h,), (None,), "near_one")}
    if kind == MAMBA:
        heads, inner = c.mamba_num_heads, c.d_inner
        return {
            **norm,
            "w_in": ((h, inner + c.conv_channels + heads),
                     ("embed", "heads"), h),
            "conv_w": ((c.conv_kernel, c.conv_channels), (None, "heads"),
                       c.conv_kernel),
            "conv_b": ((c.conv_channels,), ("heads",), "near_zero"),
            "A_log": ((heads,), (None,), "a_log"),
            "D": ((heads,), (None,), "ones"),
            "dt_bias": ((heads,), (None,), "dt_bias"),
            "gn_w": ((inner,), ("heads",), "near_one"),
            "w_out": ((inner, h), ("heads", "embed"), "fan_in_centred"),
        }
    if kind == FULL:
        heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        return {
            **norm,
            "wq": ((h, heads * d), ("embed", "heads"), h),
            "wk": ((h, kv * d), ("embed", "kv"), h),
            "wv": ((h, kv * d), ("embed", "kv"), h),
            "wo": ((heads * d, h), ("heads", "embed"), "fan_in_centred"),
        }
    ffn = {**stack.relu2_shapes("experts", h, c.moe_intermediate_size,
                                c.n_routed_experts),
           **stack.relu2_shapes("shared", h,
                                c.moe_shared_expert_intermediate_size)}
    for name in ("experts_down", "shared_down"):
        ffn[name] = ffn[name][:2] + ("fan_in_centred",)
    return {
        **norm,
        "router_w": ((h, c.router_width), ("embed", None), h),
        "router_bias": ((c.router_width,), (None,), "select_bias"),
        **ffn,
    }


def _fan_in_centred(key, shape):
    """A matrix [.., fan-in, out]: normal x (1 / sqrt(fan-in)), every output
    column's mean over the fan-in taken off."""
    w = jax.random.normal(key, shape)
    return (w - jnp.mean(w, axis=-2, keepdims=True)) / math.sqrt(shape[-2])


def _top_shapes(c: SsdMoEConfig) -> Dict[str, Tuple]:
    table = ((c.vocab_size, c.hidden_size), ("vocab", "embed"), c.hidden_size)
    return {"tok_embed": table, "lm_head": table,
            "final_norm_w": ((c.hidden_size,), (None,), "near_one")}


_PARAMS = stack.Params(
    stack.one_kind(segments), _layer_shapes, _top_shapes, {
        "near_one": lambda key, shape: (
            1.0 + 0.1 * jax.random.normal(key, shape)),
        "near_zero": lambda key, shape: 0.1 * jax.random.normal(key, shape),
        "select_bias": lambda key, shape: (
            0.01 * jax.random.normal(key, shape)),
        "a_log": lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, minval=1.0, maxval=16.0)),
        "dt_bias": _dt_bias,
        "fan_in_centred": _fan_in_centred})
logical_axes, num_params = _PARAMS.logical_axes, _PARAMS.num_params


def init_params(config: SsdMoEConfig, key) -> Dict[str, Any]:
    """{"tok_embed", "layers": {segNN: {"0": layer parameters stacked on a
    leading repeats axis}}, "final_norm_w", "lm_head" [vocab, hidden]}."""
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    return _PARAMS.init(config, {"tok_embed": k_embed, "lm_head": k_head,
                                 "final_norm_w": k_norm, "layers": k_layers})


def not_trained(config: SsdMoEConfig) -> Dict[str, Any]:
    """True at the leaves a train step leaves as they are: the router's
    selection bias."""
    return _PARAMS.tree(config, lambda name, spec: name == "router_bias")


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    """x / rms(x) w over the last axis, in float32, in x's dtype."""
    xf = x.astype(F32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w.astype(F32)).astype(x.dtype)


_matmul = stack.matmul


def ssd_scan(x, dt, a, B, C, D, config: SsdMoEConfig):
    """The mamba mixer's recurrence ALONE, on its operands as the mixer
    makes them (x [b, s, heads, P]; dt [b, s, heads] float32; a and D
    [heads] float32; B, C [b, s, groups, N]) -> y like x: the kernels of
    ops/ssd_scan.py as the mixer calls them.  The operands go in the dtype
    they come in (the mixer's are the compute dtype's)."""
    from ray_tpu.ops.ssd_scan import ssd_scan as scan

    return scan(x, dt, a, B, C, D, chunk=config.chunk_size)


def _mamba_mixer(u, lp, c: SsdMoEConfig):
    """u [b, s, hidden], the normed input -> the mixer's output."""
    b, s, _ = u.shape
    heads, P, inner = c.mamba_num_heads, c.mamba_head_dim, c.d_inner
    groups, N = c.n_groups, c.ssm_state_size
    wide = c.conv_channels
    w = lp["w_in"].astype(c.dtype)
    z = _matmul(u, w[:, :inner], c)
    xBC = with_logical_constraint(_matmul(u, w[:, inner:inner + wide], c),
                                  ("batch", "seq", "heads"))
    # the step feeds the float32 recurrence: float32 out of the MXU
    dt = _matmul(u, w[:, inner + wide:], c, F32)
    # both halves of the chain round the recurrence are ops/mixer_chain.py's
    # passes over row tiles (float32 from the operands as they lie, rounded
    # once where a result leaves); x, B, C and y cross as [b, s, columns]
    with jax.named_scope(common.SSM_CHAIN):
        x, B, C = conv_silu_split(xBC, lp["conv_w"], lp["conv_b"],
                                  (inner, groups * N, groups * N))
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))
    y = ssd_scan(
        x.reshape(b, s, heads, P), dt, -jnp.exp(lp["A_log"].astype(F32)),
        B.reshape(b, s, groups, N), C.reshape(b, s, groups, N),
        lp["D"].astype(F32), c).reshape(b, s, inner)
    with jax.named_scope(common.SSM_CHAIN):
        y = gated_group_norm(y, z, lp["gn_w"], groups, c.layer_norm_epsilon)
    return _matmul(y, lp["w_out"], c)


def _full_attention(u, lp, c: SsdMoEConfig):
    """u [b, s, hidden], the normed input -> the mixer's output."""
    from ray_tpu.ops.attention import flash_attention

    b, s, _ = u.shape
    heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = with_logical_constraint(_matmul(u, lp["wq"], c),
                                ("batch", "seq", "heads"))
    # the kernels take expanded heads, query head j reading KV head
    # j // group, as the projections lay them (`common.repeat_heads`)
    k, v = (common.repeat_heads(_matmul(u, lp[n], c), kv, heads // kv)
            .reshape(b, s, heads, d) for n in ("wk", "wv"))
    a = flash_attention(q.reshape(b, s, heads, d), k, v, causal=True,
                        sm_scale=1.0 / math.sqrt(d))
    return _matmul(a.reshape(b, s, heads * d), lp["wo"], c)


def _routed_part(flat, router_w, router_bias, w_up, w_down, c: SsdMoEConfig):
    """`stack.routed_part` behind the sigmoid router, the experts ungated:
    flat [T, hidden] -> (the held experts' sum, the routing counts)."""
    dispatch.record("ssd_moe.experts",
                    f"relu2,ungated,k{c.num_experts_per_tok}of"
                    f"{c.router_width},held{c.n_routed_experts}")
    return stack.routed_part(
        flat, lambda: moe.sigmoid_route(
            flat, router_w, router_bias,
            num_experts_per_token=c.num_experts_per_tok,
            scale=c.routed_scaling_factor),
        None, w_up, w_down, c, USUAL_LOAD)


def _layer(x, lp, _, *, kind: str, c: SsdMoEConfig):
    """One layer, ONE sublayer -> (x, an expert layer's routing counts or
    None)."""
    stats = None

    def normed():
        return with_logical_constraint(
            rms_norm(x, lp["ln_w"], c.layer_norm_epsilon),
            ("batch", "seq", "embed"))

    if kind == MAMBA:
        with jax.named_scope(common.SSM):
            out = _mamba_mixer(normed(), lp, c)
    elif kind == FULL:
        with jax.named_scope(common.ATTN_FULL):
            out = _full_attention(normed(), lp, c)
    else:
        with jax.named_scope(common.MLP):
            u = normed()
        routed, stats = _routed_part(
            u.reshape(-1, u.shape[-1]), lp["router_w"], lp["router_bias"],
            lp["experts_up"], lp["experts_down"], c)
        out = routed.reshape(u.shape) + common.relu2_mlp(
            u, lp["shared_up"], lp["shared_down"], c.dtype)
    return with_logical_constraint(x + out, ("batch", "seq", "embed")), stats


def forward_hidden(params: Dict[str, Any], tokens, config: SsdMoEConfig):
    """Embedding + layers + the final norm: [b, s] -> ([b, s, hidden], the
    LAST expert layer's routing counts and the rows all the expert layers
    held together, or None without one)."""
    c = config
    x = common.embed_tokens(params["tok_embed"], tokens, c.dtype)
    x, stats = stack.walk(_layer, c, segments(c), params["layers"], x,
                          lambda kind: None)
    with jax.named_scope(common.LOSS):
        return rms_norm(x, params["final_norm_w"],
                        c.layer_norm_epsilon), stats


_TAIL = stack.LossTail(forward_hidden, head="lm_head")
token_nll, loss_and_metrics = _TAIL.token_nll, _TAIL.loss_and_metrics
loss_fn = _TAIL.loss_fn
