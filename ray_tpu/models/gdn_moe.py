"""Gated-delta-rule linear attention beside gated full attention, with a
softmax-routed expert layer and a gated shared expert in every layer (the
`qwen3_next` form, as Qwen3-Next-80B-A3B publishes it) for training through
`ShardedTrainStep`, on models/stack.py's layer stack; embedding,
cross-entropy and SwiGLU are models/common.py's, the routed experts
models/moe.py's dropless layer, the recurrence ops/gated_delta.py's kernels,
the chain before it ops/mixer_chain.py's, attention ops/attention.py's flash
kernels.

Layer equations (x the layer's input [s, hidden]; every matrix [in, out], no
bias anywhere; every norm an RMSNorm with eps `rms_norm_eps`; Z(x; w) = x /
rms(x) (1 + w) the family's ZERO-CENTRED norm):

  block      x = x + Mixer(Z(x; w1)); x = x + FFN(Z(x; w2)); a final Z;
             logits through an UNTIED head.  Layer i is full attention
             where (i + 1) % `full_attention_interval` == 0, else linear.
  linear     u the normed input; H_k = `linear_num_key_heads` key heads of
             d_k, H_v = `linear_num_value_heads` value heads of d_v.
             [q | k | v | z] = u W_qkvz (widths H_k d_k, H_k d_k, H_v d_v,
             H_v d_v), [b | a] = u W_ba (H_v each).  [q | k | v] <- silu(
             causal depthwise conv over `linear_conv_kernel_dim` taps, no
             bias).  beta = sigmoid(b); g = -exp(A_log) softplus(a +
             dt_bias), float32, one a value head.  q <- l2norm(q) /
             sqrt(d_k), k <- l2norm(k) over a head's columns (eps 1e-6);
             value head j reads key head j // (H_v / H_k).  A head, float32,
             S [d_k, d_v] from zero: S <- exp(g_t) S; d_t = beta_t (v_t -
             S^T k_t); S <- S + k_t d_t^T; o_t = S^T q_t.  y = (o / rms(o)
             w_n) silu(z) a head (w_n [d_v], a PLAIN weight), then W_o.
  full       [q | gate] = u W_q, a head's `head_dim` query columns then its
             `head_dim` gate columns; k = u W_k, v = u W_v over
             `num_key_value_heads`; q <- Z(q; w_q), k <- Z(k; w_k) a head;
             rope, half-split pairing, on the FIRST head_dim x
             `partial_rotary_factor` columns; causal softmax attention at
             1 / sqrt(head_dim), query head j reading KV head j // group;
             (attention x sigmoid(gate)) W_o: the gate an ELEMENT's.
  FFN        p = softmax(y W_r) in float32 over `router_width` experts; the
             top `num_experts_per_tok`; gates p[sel] / sum(p[sel]); sum over
             the chosen experts HELD HERE of gate_e SwiGLU_e(y) + sigmoid(y
             w_sg) SwiGLU_shared(y).  No auxiliary loss.

One chip's share (`stack.routed_part`): `num_experts` experts held HERE,
from `first_held_expert` on, of `router_width` routed over.  The segments
are maximal runs of layers of one kind: the published pattern is two a
period, three linear layers and one full.

How the full layer's quarter rope reaches the kernels, whose rope turns the
WHOLE head: W_q's and W_k's columns and the q / k norms' weights are
reordered AT USE (`stack.rotary_first`; the norm over a head and q . k do not
see one permutation of both) and the tables have an identity tail
(`stack.kernel_tables`; `dispatch.taken()["gdn_moe.rope"]`).  The same
reordering of W_q splits a head's query columns from its gate columns, so the
activations are never sliced by head.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common, moe, stack
from ray_tpu.models.swa_moe import USUAL_LOAD
from ray_tpu.ops import dispatch
from ray_tpu.ops.mixer_chain import L2_EPS, conv_silu_l2norm
from ray_tpu.parallel.sharding import with_logical_constraint

F32 = jnp.float32
FULL, LINEAR = "full_attention", "linear_attention"


@dataclasses.dataclass(frozen=True)
class GdnMoEConfig:
    """The published config.json's key names, the chip's share
    (`router_width`, `first_held_expert`) and the train switches the other
    models have."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: Any = None
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    hidden_act: str = "silu"
    mlp_only_layers: Tuple[int, ...] = ()
    decoder_sparse_step: int = 1
    use_sliding_window: bool = False
    num_experts: int = 512              # held HERE
    router_width: Optional[int] = None  # routed over; None: all are held
    first_held_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    fused_ce: bool = False

    def __post_init__(self):
        put = functools.partial(object.__setattr__, self)
        if self.router_width is None:
            put("router_width", self.num_experts)
        put("mlp_only_layers", tuple(self.mlp_only_layers))
        unsupported = {
            "rope_scaling": self.rope_scaling is not None,
            "hidden_act": self.hidden_act != "silu",
            "mlp_only_layers": bool(self.mlp_only_layers),
            "decoder_sparse_step": self.decoder_sparse_step != 1,
            "use_sliding_window": self.use_sliding_window,
            "norm_topk_prob": not self.norm_topk_prob,
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(f"not written down here, so not computed: {bad}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("query / value heads must be a multiple of the "
                             "KV / key heads")
        if self.first_held_expert + self.num_experts > self.router_width:
            raise ValueError("the held experts lie outside the router's")
        r, d = self.rotary_width, self.head_dim
        if r % 2 or not 0 < r <= d or (d - r) % 4:
            raise ValueError(f"rope pairs dimensions and the rest is cut in "
                             f"two: it turns {r} of {d}")

    @property
    def rotary_width(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def experts_held(self) -> Tuple[int, int]:
        return self.first_held_expert, self.num_experts

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0
                     else LINEAR for i in range(self.num_hidden_layers))

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @classmethod
    def tiny(cls, **kw) -> "GdnMoEConfig":
        """Test-sized: one period of the published pattern (three linear
        layers, one full), 2 key / 4 value heads, group 2 in the full layer,
        a rotary quarter of a head of 64, 4 of 16 experts held."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=64,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=32, linear_value_head_dim=32,
            num_experts=4, router_width=16, num_experts_per_tok=3,
            moe_intermediate_size=32, shared_expert_intermediate_size=32),
            **kw})


def segments(config: GdnMoEConfig) -> List[Tuple[str, int, int]]:
    """(kind, first layer, repeats): maximal runs of layers of one kind."""
    return stack.runs(config.layer_types)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
# Norm weights, A_log and dt_bias are DRAWN, not constants: a program that
# reads a zero-centred weight as a plain one, or drops dt_bias, then differs
# from the reference on every seed.  A and the step follow the family's
# published initialisation: A uniform in (0, 16), dt_bias the inverse
# softplus of a step drawn log-uniformly in (0.001, 0.1).

def _layer_shapes(kind: str, c: GdnMoEConfig) -> Dict[str, Tuple]:
    """name -> (shape, logical axes, init): init is a matrix's fan-in, or
    how a vector is drawn ("zero_centred", "near_one", "a_log", "dt_bias")."""
    h = c.hidden_size
    if kind == LINEAR:
        hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
        mixer = {
            "w_qkvz": ((h, c.conv_channels + hv * dv), ("embed", "heads"), h),
            "w_ba": ((h, 2 * hv), ("embed", None), h),
            "conv_w": ((c.linear_conv_kernel_dim, c.conv_channels),
                       (None, "heads"), c.linear_conv_kernel_dim),
            "A_log": ((hv,), (None,), "a_log"),
            "dt_bias": ((hv,), (None,), "dt_bias"),
            "gn_w": ((dv,), (None,), "near_one"),
            "wo": ((hv * dv, h), ("heads", "embed"), hv * dv),
        }
    else:
        heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        mixer = {
            "wq": ((h, heads * 2 * d), ("embed", "heads"), h),
            "wk": ((h, kv * d), ("embed", "kv"), h),
            "wv": ((h, kv * d), ("embed", "kv"), h),
            "q_norm_w": ((d,), (None,), "zero_centred"),
            "k_norm_w": ((d,), (None,), "zero_centred"),
            "wo": ((heads * d, h), ("heads", "embed"), heads * d),
        }
    return {
        "ln1_w": ((h,), (None,), "zero_centred"), **mixer,
        "ln2_w": ((h,), (None,), "zero_centred"),
        "router_w": ((h, c.router_width), ("embed", None), h),
        **stack.swiglu_shapes("experts", h, c.moe_intermediate_size,
                              c.num_experts),
        **stack.swiglu_shapes("shared", h,
                              c.shared_expert_intermediate_size),
        "shared_expert_gate": ((h, 1), ("embed", None), h),
    }


def _dt_bias(key, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))        # softplus(x) = dt


def _top_shapes(c: GdnMoEConfig) -> Dict[str, Tuple]:
    table = ((c.vocab_size, c.hidden_size), ("vocab", "embed"), c.hidden_size)
    return {"tok_embed": table, "lm_head": table,
            "final_norm_w": ((c.hidden_size,), (None,), "zero_centred")}


_PARAMS = stack.Params(
    stack.one_kind(segments), _layer_shapes, _top_shapes, {
        "zero_centred": lambda key, shape: (
            0.1 * jax.random.normal(key, shape)),
        "near_one": lambda key, shape: (
            1.0 + 0.1 * jax.random.normal(key, shape)),
        "a_log": lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, minval=1e-2, maxval=16.0)),
        "dt_bias": _dt_bias,
        "fan_in": lambda key, shape, fan_in: (
            jax.random.normal(key, shape) / math.sqrt(fan_in))})
logical_axes, num_params = _PARAMS.logical_axes, _PARAMS.num_params


def init_params(config: GdnMoEConfig, key) -> Dict[str, Any]:
    """{"tok_embed", "layers": {segNN: {"0": layer parameters stacked on a
    leading repeats axis}}, "final_norm_w", "lm_head" [vocab, hidden]}."""
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    return _PARAMS.init(config, {"tok_embed": k_embed, "lm_head": k_head,
                                 "final_norm_w": k_norm, "layers": k_layers})


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def zero_centred_norm(x, w, eps):
    """x / rms(x) (1 + w) over the last axis, in float32, in x's dtype."""
    xf = x.astype(F32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * (1.0 + w.astype(F32))).astype(x.dtype)


_matmul, _per_head = stack.matmul, stack.per_head


def gated_delta_rule(q, k, v, g, beta, config: GdnMoEConfig):
    """The linear mixer's recurrence ALONE, on its operands as the mixer
    makes them (q, k [b, s, key heads, d_k], normalised; v [b, s, value
    heads, d_v]; g, beta [b, s, value heads] float32) -> o like v: the
    kernels of ops/gated_delta.py as the mixer calls them.  The operands go
    in the dtype they come in (the mixer's are the compute dtype's)."""
    del config
    from ray_tpu.ops.gated_delta import gated_delta_rule as rule

    return rule(q, k, v, g, beta)


def _linear_mixer(x, lp, c: GdnMoEConfig):
    """x [b, s, hidden], the layer's input -> the mixer's output."""
    b, s, _ = x.shape
    hk, dk = c.linear_num_key_heads, c.linear_key_head_dim
    hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
    wide = c.conv_channels

    def normed(x):
        return with_logical_constraint(
            zero_centred_norm(x, lp["ln1_w"], c.rms_norm_eps),
            ("batch", "seq", "embed"))

    def rule_of(x, w_qkv, conv_w, g, beta):
        qkv = with_logical_constraint(_matmul(normed(x), w_qkv, c),
                                      ("batch", "seq", "heads"))
        with jax.named_scope(common.SSM_CHAIN):
            q, k, v = conv_silu_l2norm(qkv, conv_w, hk, dk,
                                       1.0 / math.sqrt(dk), L2_EPS)
        # [b, s, heads x d] on the way out, as the kernel wrote it: a [b, s,
        # heads, d] view that crosses the checkpoint is re-laid
        return gated_delta_rule(
            q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
            v.reshape(b, s, hv, dv), g, beta, c).reshape(b, s, hv * dv)

    if c.remat:
        # Under the layer's remat qkv, q, k and v are made AGAIN from the
        # layer's input for the rule's backward, and do not lie on the chip
        # while the layer's experts work; the rule's o and states are kept,
        # so its forward kernel is not run again.  XLA's own
        # rematerialisation did this to the chain while the chain was its
        # fusions, and cannot do it to a kernel's results: without it the
        # step program reads 0.7 GiB more and keeps no flash out and lse
        # (PERF.md, PR 46).
        from ray_tpu.ops.gated_delta import KEPT_NAMES

        rule_of = jax.checkpoint(
            rule_of,
            policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))
    u = normed(x)
    w = lp["w_qkvz"].astype(c.dtype)
    z = _matmul(u, w[:, wide:], c)
    ba = _matmul(u, lp["w_ba"], c, F32)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["A_log"].astype(F32)) * jax.nn.softplus(
        ba[..., hv:] + lp["dt_bias"].astype(F32))
    o = rule_of(x, w[:, :wide], lp["conv_w"], g, beta)
    gn_w = lp["gn_w"].astype(F32)
    y = _per_head(o, hv, lambda t: t * jax.lax.rsqrt(
        jnp.mean(t * t, axis=-1, keepdims=True) + c.rms_norm_eps) * gn_w)
    y = (y.astype(F32) * jax.nn.silu(z.astype(F32))).astype(c.dtype)
    return _matmul(y, lp["wo"], c)


def _rotary_first(x, c: GdnMoEConfig):
    """The last axis, ONE head's columns as published -> as the flash
    kernels' rope pairs them (`stack.rotary_first`)."""
    return stack.rotary_first(x, 1, c.rotary_width)


def kernel_tables(seq: int, c: GdnMoEConfig):
    """(cos, sin) [seq, head_dim / 2] float32 as the flash kernels take
    them for a head ordered by `_rotary_first` (`stack.kernel_tables`)."""
    return stack.kernel_tables(
        *stack.rope_tables(seq, c.rotary_width, c.rope_theta), c.head_dim)


def _full_attention(u, lp, tables, c: GdnMoEConfig):
    """u [b, s, hidden], the normed input -> the mixer's output."""
    from ray_tpu.ops.attention import flash_attention

    b, s, h = u.shape
    heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    # W_q's columns, a head's [query | gate], as all the queries (each head
    # ordered for the kernels' rope) and all the gates
    wq = lp["wq"].astype(c.dtype).reshape(h, heads, 2, d)
    w_query = _rotary_first(wq[:, :, 0], c).reshape(h, heads * d)
    w_gate = wq[:, :, 1].reshape(h, heads * d)
    wk = _rotary_first(lp["wk"].astype(c.dtype).reshape(h, kv, d), c)
    q_w, k_w = (_rotary_first(lp[n].astype(F32), c)
                for n in ("q_norm_w", "k_norm_w"))

    def normed(x, n, w):
        return _per_head(x, n, lambda t: zero_centred_norm(
            t, w, c.rms_norm_eps))

    q = with_logical_constraint(_matmul(u, w_query, c),
                                ("batch", "seq", "heads"))
    q = normed(q, heads, q_w).reshape(b, s, heads, d)
    k = normed(_matmul(u, wk.reshape(h, kv * d), c), kv, k_w)
    # the kernels take expanded heads, query head j reading KV head
    # j // group, as the projections lay them (`common.repeat_heads`)
    k, v = (common.repeat_heads(x, kv, heads // kv).reshape(b, s, heads, d)
            for x in (k, _matmul(u, lp["wv"], c)))
    rope = tuple(jnp.broadcast_to(t, (b, *t.shape)) for t in tables)
    a = flash_attention(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(d),
                        rope=rope)
    with jax.named_scope(common.ATTN_GATE):
        gate = jax.nn.sigmoid(_matmul(u, w_gate, c, F32))
        a = (a.reshape(b, s, heads * d).astype(F32) * gate).astype(c.dtype)
    return _matmul(a, lp["wo"], c)


def _routed_part(flat, router_w, w_gate, w_up, w_down, c: GdnMoEConfig):
    """`stack.routed_part` behind the softmax router: flat [T, hidden] ->
    (the held experts' sum, the routing counts)."""
    return stack.routed_part(
        flat, lambda: moe.softmax_route(
            flat, router_w, num_experts_per_token=c.num_experts_per_tok,
            scale=1.0),
        w_gate, w_up, w_down, c, USUAL_LOAD)


def _layer(x, lp, tables, *, kind: str, c: GdnMoEConfig):
    """One layer -> (x, the expert layer's routing counts)."""
    if kind == FULL:
        with jax.named_scope(common.ATTN_FULL):
            u = zero_centred_norm(x, lp["ln1_w"], c.rms_norm_eps)
            u = with_logical_constraint(u, ("batch", "seq", "embed"))
            mixed = _full_attention(u, lp, tables, c)
    else:
        with jax.named_scope(common.SSM):
            mixed = _linear_mixer(x, lp, c)
    x = with_logical_constraint(x + mixed, ("batch", "seq", "embed"))
    with jax.named_scope(common.MLP):
        y = zero_centred_norm(x, lp["ln2_w"], c.rms_norm_eps)
    routed, stats = _routed_part(
        y.reshape(-1, y.shape[-1]), lp["router_w"], lp["experts_gate"],
        lp["experts_up"], lp["experts_down"], c)
    shared = common.swiglu(y, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"], c.dtype)
    with jax.named_scope(common.MLP):
        open_ = jax.nn.sigmoid(_matmul(y, lp["shared_expert_gate"], c, F32))
        shared = (shared.astype(F32) * open_).astype(c.dtype)
    ffn = routed.reshape(y.shape) + shared
    return with_logical_constraint(x + ffn, ("batch", "seq", "embed")), stats


def forward_hidden(params: Dict[str, Any], tokens, config: GdnMoEConfig):
    """Embedding + layers + the final norm: [b, s] -> ([b, s, hidden], the
    LAST layer's routing counts and the rows all the expert layers held
    together)."""
    c = config
    x = common.embed_tokens(params["tok_embed"], tokens, c.dtype)
    tables = None
    if FULL in c.layer_types:
        with jax.named_scope(common.ATTN_FULL):     # the tables are its own
            tables = kernel_tables(tokens.shape[1], c)
        dispatch.record("gdn_moe.rope",
                        stack.rope_word(FULL, c.rotary_width, c.head_dim))
    x, stats = stack.walk(_layer, c, segments(c), params["layers"], x,
                          lambda kind: tables)
    with jax.named_scope(common.LOSS):
        return zero_centred_norm(x, params["final_norm_w"],
                                 c.rms_norm_eps), stats


_TAIL = stack.LossTail(forward_hidden, head="lm_head")
token_nll, loss_and_metrics = _TAIL.token_nll, _TAIL.loss_and_metrics
loss_fn = _TAIL.loss_fn
