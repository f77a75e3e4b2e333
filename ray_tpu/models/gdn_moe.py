"""Gated-delta-rule linear attention beside gated full attention, with a
softmax-routed expert layer and a gated shared expert in every layer (the
`qwen3_next` form, as Qwen3-Next-80B-A3B publishes it) for training through
`ShardedTrainStep`: the same entry points as the other model files
(`init_params`, `logical_axes`, `num_params`, `loss_fn`, `token_nll`,
`loss_and_metrics`); embedding, fused cross-entropy, SwiGLU, the causal
convolution and the remat wrapper are models/common.py's, the routed experts
models/moe.py's dropless layer, the recurrence ops/gated_delta.py's kernels,
attention ops/attention.py's flash kernels.

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

One chip's share: `num_experts` is how many experts THIS program holds
(experts `first_held_expert` on), `router_width` how many the model routes
over: models/swa_moe.py's convention, and its parameter tree
(`params["layers"][segNN]["0"][leaf][repeat]`: maximal runs of layers of one
kind, stacked and scanned).  The published pattern is two segments a period:
three linear layers, one full.

How the full layer's quarter rope reaches the kernels.  `flash_attention(..,
rope=)` turns column i with column i + d/2 over the WHOLE head.  The
published head is [rot_a | rot_b | pass] (r/2, r/2, d - r columns, the
rotary pair i being (rot_a[i], rot_b[i])); W_q's and W_k's columns and the q
/ k norms' weights are reordered AT USE to [rot_a | pass' | rot_b | pass'']
(the pass-through columns cut in two), and the tables hold cos 1 and sin 0
for them, so the kernel's whole-head turn is the published quarter turn and
the identity on the rest (`dispatch.taken()["gdn_moe.rope"]`).  The norm over
a head and q . k do not see one permutation of both.  The same reordering of
W_q splits a head's query columns from its gate columns, so the activations
are never sliced by head.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common, moe
from ray_tpu.ops import dispatch
from ray_tpu.ops.mixer_chain import L2_EPS, conv_silu_l2norm
from ray_tpu.parallel.sharding import with_logical_constraint

F32 = jnp.float32
FULL, LINEAR = "full_attention", "linear_attention"
# The usual buffer of an expert layer, in rows even routing would send to
# the held experts (models/swa_moe.py has the reason and its readings).
USUAL_LOAD = 4


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
    out: List[Tuple[str, int, int]] = []
    for i, kind in enumerate(config.layer_types):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, i, 1))
    return out


def _segment_name(i: int) -> str:
    return f"seg{i:02d}"


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
    m, held = c.moe_intermediate_size, c.num_experts
    shared = c.shared_expert_intermediate_size
    return {
        "ln1_w": ((h,), (None,), "zero_centred"), **mixer,
        "ln2_w": ((h,), (None,), "zero_centred"),
        "router_w": ((h, c.router_width), ("embed", None), h),
        "experts_gate": ((held, h, m), ("expert", "embed", "mlp"), h),
        "experts_up": ((held, h, m), ("expert", "embed", "mlp"), h),
        "experts_down": ((held, m, h), ("expert", "mlp", "embed"), m),
        "shared_gate": ((h, shared), ("embed", "mlp"), h),
        "shared_up": ((h, shared), ("embed", "mlp"), h),
        "shared_down": ((shared, h), ("mlp", "embed"), shared),
        "shared_expert_gate": ((h, 1), ("embed", None), h),
    }


def _draw(key, shape, init, dtype):
    if init == "zero_centred":
        x = 0.1 * jax.random.normal(key, shape)
    elif init == "near_one":
        x = 1.0 + 0.1 * jax.random.normal(key, shape)
    elif init == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, minval=1e-2, maxval=16.0))
    elif init == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))       # softplus(x) = dt
    else:
        x = jax.random.normal(key, shape) / math.sqrt(init)
    return x.astype(dtype)


def _init_layer(key, kind: str, c: GdnMoEConfig) -> Dict[str, Any]:
    shapes = _layer_shapes(kind, c)
    return {name: _draw(k, shape, init, c.param_dtype)
            for k, (name, (shape, _, init)) in zip(
                jax.random.split(key, len(shapes)), shapes.items())}


def init_params(config: GdnMoEConfig, key) -> Dict[str, Any]:
    """{"tok_embed", "layers": {segNN: {"0": layer parameters stacked on a
    leading repeats axis}}, "final_norm_w", "lm_head" [vocab, hidden]}."""
    c = config
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    layers = {}
    for si, (kind, first, repeats) in enumerate(segments(c)):
        each = [_init_layer(jax.random.fold_in(k_layers, first + rep), kind, c)
                for rep in range(repeats)]
        layers[_segment_name(si)] = {
            "0": jax.tree.map(lambda *a: jnp.stack(a), *each)}
    table = (c.vocab_size, c.hidden_size)
    return {
        "tok_embed": _draw(k_embed, table, c.hidden_size, c.param_dtype),
        "layers": layers,
        "final_norm_w": _draw(k_norm, (c.hidden_size,), "zero_centred",
                              c.param_dtype),
        "lm_head": _draw(k_head, table, c.hidden_size, c.param_dtype),
    }


def logical_axes(config: GdnMoEConfig) -> Dict[str, Any]:
    """Logical-axis tree matching init_params, for parallel.sharding."""
    layers = {
        _segment_name(si): {"0": {
            name: ("layers",) + axes
            for name, (_, axes, _) in _layer_shapes(kind, config).items()}}
        for si, (kind, _, _) in enumerate(segments(config))}
    return {"tok_embed": ("vocab", "embed"), "layers": layers,
            "final_norm_w": (None,), "lm_head": ("vocab", "embed")}


def num_params(config: GdnMoEConfig) -> int:
    per_layer = sum(math.prod(shape) for kind in config.layer_types
                    for shape, _, _ in _layer_shapes(kind, config).values())
    return (2 * config.vocab_size * config.hidden_size + per_layer
            + config.hidden_size)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def zero_centred_norm(x, w, eps):
    """x / rms(x) (1 + w) over the last axis, in float32, in x's dtype."""
    xf = x.astype(F32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * (1.0 + w.astype(F32))).astype(x.dtype)


def _matmul(x, w, c: GdnMoEConfig, out_dtype=None):
    """bf16 operands, fp32 accumulation, the result in the compute dtype."""
    return jnp.einsum("bsi,io->bso", x.astype(c.dtype), w.astype(c.dtype),
                      preferred_element_type=out_dtype or c.dtype)


def _per_head(x, heads: int, fn):
    """fn over every head's columns of x [b, s, heads x w], by whole tiles
    (`common.by_tiles`): fn sees [.., w] float32 and gives the like."""
    return common.from_tiles(fn(common.by_tiles(x, heads).astype(F32))
                             .astype(x.dtype))


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
    """The last axis, a head's columns as published, [rot_a | rot_b | pass]
    -> [rot_a | pass' | rot_b | pass'']: pair i is then (i, i + d/2) of the
    whole head, which the flash kernels' rope turns.  Four slices, exact;
    the gradient puts them back."""
    d, r = c.head_dim, c.rotary_width
    if r == d:
        return x
    cut = r + (d - r) // 2
    return jnp.concatenate([x[..., :r // 2], x[..., r:cut],
                            x[..., r // 2:r], x[..., cut:]], axis=-1)


def kernel_tables(seq: int, c: GdnMoEConfig):
    """(cos, sin) [seq, head_dim / 2] float32 as the flash kernels take
    them for a head ordered by `_rotary_first`: the rotary pairs' cos and
    sin at positions 0 .. seq - 1, then cos 1 and sin 0 for the pairs that
    pass through."""
    r = c.rotary_width
    inv_freq = 1.0 / float(c.rope_theta) ** (
        2.0 * jnp.arange(r // 2, dtype=F32) / r)
    angle = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
    passing = (c.head_dim - r) // 2
    return (jnp.concatenate([jnp.cos(angle), jnp.ones((seq, passing), F32)],
                            axis=1),
            jnp.concatenate([jnp.sin(angle), jnp.zeros((seq, passing), F32)],
                            axis=1))


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
    """The router and models/moe.py's dropless layer for this chip's share:
    flat [T, hidden] -> (the held experts' sum, the routing counts).  The
    usual buffer holds `USUAL_LOAD` times the rows even routing sends here;
    a step that sends more takes the full bound's."""
    with jax.named_scope(common.MOE_ROUTE):
        idx, gates = moe.softmax_route(
            flat, router_w, num_experts_per_token=c.num_experts_per_tok,
            scale=1.0)
    even = -(-flat.shape[0] * c.num_experts_per_tok * c.num_experts
             // c.router_width)
    return moe.routed_experts(
        flat, idx, gates, w_gate, w_up, w_down, experts_held=c.experts_held,
        dtype=c.dtype, usual_rows=USUAL_LOAD * even)


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


@functools.cache
def _layer_fn(kind: str, c: GdnMoEConfig):
    return common.maybe_remat(functools.partial(_layer, kind=kind, c=c),
                              c.remat, c.remat_policy)


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
        dispatch.record("gdn_moe.rope", (
            f"{FULL}:in_kernel{c.rotary_width}of{c.head_dim}"
            + ("" if c.rotary_width == c.head_dim
               else "_columns_reordered_at_use_identity_tail")))
    stats, rows_held = None, 0
    for si, (kind, _, _) in enumerate(segments(c)):
        fn = _layer_fn(kind, c)

        def body(x, lp, fn=fn):
            return fn(x, lp, tables)

        x, per_layer = jax.lax.scan(
            body, x, params["layers"][_segment_name(si)]["0"])
        stats = jax.tree.map(lambda a: a[-1], per_layer)
        rows_held = rows_held + jnp.sum(per_layer["rows_held"])
    stats["rows_held_all_layers"] = rows_held
    with jax.named_scope(common.LOSS):
        return zero_centred_norm(x, params["final_norm_w"],
                                 c.rms_norm_eps), stats


def _nll_and_stats(params, batch, config: GdnMoEConfig):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, stats = forward_hidden(params, inputs, config)
    if config.fused_ce:
        return common.fused_nll(x, params["lm_head"], targets), stats
    logits = common.tied_logits(x, params["lm_head"], config.dtype)
    return common.logits_nll(logits, targets), stats


def token_nll(params, batch, config: GdnMoEConfig):
    """-log p(tokens[t+1] | tokens[:t+1]) for every position: [b, s] fp32.
    batch: {"tokens": [b, s+1] int32}."""
    return _nll_and_stats(params, batch, config)[0]


def loss_and_metrics(params, batch, config: GdnMoEConfig):
    """(next-token cross-entropy, the LAST layer's routing counts as `moe_*`
    device scalars)."""
    nll, stats = _nll_and_stats(params, batch, config)
    mask = batch.get("mask")
    loss = common.masked_mean(nll, None if mask is None else mask[:, 1:])
    return loss, {f"moe_{k}": v for k, v in stats.items()}


def loss_fn(params, batch, config: GdnMoEConfig):
    """Next-token cross-entropy: the mean of `token_nll`, over the
    positions batch["mask"] keeps if there is one."""
    return loss_and_metrics(params, batch, config)[0]
