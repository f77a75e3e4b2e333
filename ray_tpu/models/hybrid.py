"""Hybrid decoder-decoder model (Phi-4-mini-flash-reasoning, "SambaY"):
state-space, sliding-window, full, gated-memory and cross-attention layers
in one model, for training through `ShardedTrainStep`.

The same entry points as models/transformer.py (`init_params`,
`logical_axes`, `forward`, `loss_fn`); embedding, tied head, fused
cross-entropy and SwiGLU are models/common.py's, the parameter tree, the
layer function's remat and the loss tail models/stack.py's.

Layer equations.  Every layer: h = x + Mixer(LN1(x)), out = h + MLP(LN2(h)).
LN is LayerNorm with weight and bias (eps `layer_norm_eps`);
MLP(u) = (silu(u Wg) * (u Wu)) Wd, no bias.  Embedding, the layers, a final
LayerNorm, logits = h E^T (tied).  No positional encoding anywhere.

  mamba   [x, z] = u W_in; x = silu(conv1d_causal_depthwise(x) + b_conv);
          [r, B_t, C_t] = x W_x; dt = softplus(r W_dt + b_dt);
          A = -exp(A_log); per channel c and state n
            h_t[c,n] = exp(dt_t[c] A[c,n]) h_{t-1}[c,n] + dt_t[c] x_t[c] B_t[n]
            y_t[c]   = sum_n h_t[c,n] C_t[n] + D[c] x_t[c]
          (float32, ops/selective_scan.py); out = (y * silu(z)) W_out.
          The LAST mamba layer also hands on m = y (before the gate): the
          memory.
  window, full
          [q, k, v] = u W_qkv + b; differential attention (below), causal;
          in a window layer query t sees keys s with 0 <= t - s <
          `sliding_window`; out = a W_o + b_o.  The full layer also hands
          on its k, v: the shared KV.
  gmu     out = (m * silu(u W_1)) W_2, m the memory at the same position.
  cross   q = u W_q + b; keys and values the shared KV; the same
          differential attention with its own lambda vectors and norm
          weight, causal; out = a W_o + b_o.

Differential attention.  The query heads are pairs (q1, q2) = heads (2i,
2i + 1), the KV heads pairs (k1, k2) = (2j, 2j + 1), the value heads values
of twice the head size (heads 2j and 2j + 1 side by side); query pair i
uses KV pair i // (query heads / KV heads).
  a = softmax(q1 k1^T / sqrt(d)) V - lam softmax(q2 k2^T / sqrt(d)) V,
then RMSNorm over the 2d with a learned weight, times (1 - lam_init), heads
concatenated.  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init with four
learned vectors of size d a layer; lam_init = 0.8 - 0.6 exp(-0.3 l), l the
layer's index in the model as configured.

It is built from ONE flash_attention call (no second kernel): the q1 heads
and then the q2 heads on the head axis, each against its own keys and the
pair's 2d-wide values, queries and keys zero-padded from d to 2d.  The
padding adds nothing to a score, and on the v5e a 64-wide contraction
costs the MXU what a 128-wide one does (PERF.md, PR 29), so this is half
the score work of four calls at head size d (measured: PERF.md, PR 30).

The program.  `layer_kinds` is the model's order of layers as an explicit
tuple.  Runs of (mamba, window) and of (gmu, cross) pairs hold their
parameters stacked and are scanned, so the published 32 layers do not
unroll; the memory source and the KV source stand alone.  The memory and
the shared KV are explicit values of the layer loop: the source's
checkpointed layer returns them, every reader's checkpointed layer takes
them as an argument, so per-layer remat saves them and never recomputes
them from layer 0.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common, stack
from ray_tpu.parallel.sharding import with_logical_constraint

KINDS = ("mamba", "window", "full", "gmu", "cross")
# self-decoder [mamba, window] x 8, mamba (memory source), full (KV
# source); cross-decoder [gmu, cross] x 7
PUBLISHED_LAYER_KINDS = (("mamba", "window") * 8 + ("mamba", "full")
                         + ("gmu", "cross") * 7)
_SCANNED_PAIRS = (("mamba", "window"), ("gmu", "cross"))
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The published config.json's key names, the state-space sizes the
    family's code derives, and the train switches the dense model has."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    layer_kinds: Tuple[str, ...] = PUBLISHED_LAYER_KINDS
    num_hidden_layers: Optional[int] = None     # checked against layer_kinds
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    fused_ce: bool = False

    def __post_init__(self):
        kinds = tuple(self.layer_kinds)
        object.__setattr__(self, "layer_kinds", kinds)
        unknown = sorted(set(kinds) - set(KINDS))
        if unknown:
            raise ValueError(f"unknown layer kinds {unknown}; known: {KINDS}")
        if self.num_hidden_layers not in (None, len(kinds)):
            raise ValueError(f"num_hidden_layers {self.num_hidden_layers} "
                             f"but {len(kinds)} layer_kinds")
        seen = set()
        for i, kind in enumerate(kinds):
            if kind == "gmu" and "mamba" not in seen:
                raise ValueError(f"layer {i} (gmu) has no mamba layer "
                                 f"before it to take its memory from")
            if kind == "cross" and "full" not in seen:
                raise ValueError(f"layer {i} (cross) has no full layer "
                                 f"before it to take its keys from")
            seen.add(kind)
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("differential attention pairs heads: query and "
                             "KV heads must be even, and KV divide query")

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def memory_source(self) -> Optional[int]:
        """Index of the last mamba layer: its scan output is the memory."""
        found = [i for i, k in enumerate(self.layer_kinds) if k == "mamba"]
        return found[-1] if found else None

    @classmethod
    def tiny(cls, **kw) -> "HybridConfig":
        """Test-sized: all five kinds, compiles in seconds on the CPU."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, sliding_window=16,
            layer_kinds=("mamba", "window", "mamba", "window", "mamba",
                         "full", "gmu", "cross"),
            mamba_d_state=4, mamba_dt_rank=8), **kw})


def segments(config: HybridConfig) -> List[Tuple[Tuple[str, ...], int, int]]:
    """(pattern, first layer, repeats): maximal runs of a scanned pair, and
    every other layer alone.  The memory source is never inside a run (its
    layer returns the memory); the full layer is no part of a pair."""
    kinds, out, i = config.layer_kinds, [], 0
    while i < len(kinds):
        pair, j = tuple(kinds[i:i + 2]), i
        if pair in _SCANNED_PAIRS:
            while tuple(kinds[j:j + 2]) == pair \
                    and config.memory_source not in (j, j + 1):
                j += 2
        if j > i:
            out.append((pair, i, (j - i) // 2))
            i = j
        else:
            out.append(((kinds[i],), i, 1))
            i += 1
    return out


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _mixer_shapes(kind: str, c: HybridConfig) -> Dict[str, Tuple]:
    """name -> (shape, logical axes, init): init is a fan-in for a matrix,
    or one of "zeros", "ones", "bias", "lambda", "a_log", "dt_bias"."""
    h, di, n = c.hidden_size, c.d_inner, c.mamba_d_state
    heads, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    attn_tail = {
        "wo": ((heads * hd, h), ("heads", "embed"), heads * hd),
        "bo": ((h,), (None,), "bias"),
        "lq1": ((hd,), (None,), "lambda"), "lk1": ((hd,), (None,), "lambda"),
        "lq2": ((hd,), (None,), "lambda"), "lk2": ((hd,), (None,), "lambda"),
        "subln": ((2 * hd,), (None,), "ones"),
    }
    if kind == "mamba":
        r = c.mamba_dt_rank
        return {
            "in_proj": ((h, 2 * di), ("embed", "ssm_inner"), h),
            "conv_w": ((c.mamba_d_conv, di), ("ssm_conv", "ssm_inner"),
                       c.mamba_d_conv),
            "conv_b": ((di,), ("ssm_inner",), "bias"),
            "x_proj": ((di, r + 2 * n), ("ssm_inner", None), di),
            "dt_w": ((r, di), (None, "ssm_inner"), r),
            "dt_b": ((di,), ("ssm_inner",), "dt_bias"),
            "A_log": ((di, n), ("ssm_inner", "ssm_state"), "a_log"),
            "D": ((di,), ("ssm_inner",), "ones"),
            "out_proj": ((di, h), ("ssm_inner", "embed"), di),
        }
    if kind in ("window", "full"):
        width = (heads + 2 * kv) * hd
        return {"wqkv": ((h, width), ("embed", "heads"), h),
                "bqkv": ((width,), (None,), "bias"), **attn_tail}
    if kind == "gmu":
        return {"w1": ((h, di), ("embed", "ssm_inner"), h),
                "w2": ((di, h), ("ssm_inner", "embed"), di)}
    if kind == "cross":
        return {"wq": ((h, heads * hd), ("embed", "heads"), h),
                "bq": ((heads * hd,), (None,), "bias"), **attn_tail}
    raise ValueError(kind)


def _layer_shapes(kind: str, c: HybridConfig) -> Dict[str, Tuple]:
    h = c.hidden_size
    return {
        "ln1_w": ((h,), (None,), "ones"), "ln1_b": ((h,), (None,), "zeros"),
        **_mixer_shapes(kind, c),
        "ln2_w": ((h,), (None,), "ones"), "ln2_b": ((h,), (None,), "zeros"),
        **stack.swiglu_shapes("w", h, c.intermediate_size),
    }


def _dt_bias(key, shape):    # softplus(dt_b) log-uniform in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(key, shape) * math.log(100.0)
                 + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def _top_shapes(c: HybridConfig) -> Dict[str, Tuple]:
    h = c.hidden_size
    return {"tok_embed": ((c.vocab_size, h), ("vocab", "embed"), h),
            "final_norm_w": ((h,), (None,), "ones"),
            "final_norm_b": ((h,), (None,), "zeros")}


_PARAMS = stack.Params(segments, _layer_shapes, _top_shapes, {
    "bias": lambda key, shape: jax.random.normal(key, shape) * 0.02,
    "lambda": lambda key, shape: jax.random.normal(key, shape) * 0.1,
    # A = -(1 .. state) for every channel
    "a_log": lambda key, shape: jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=F32)), shape),
    "dt_bias": _dt_bias})
logical_axes, num_params = _PARAMS.logical_axes, _PARAMS.num_params


def init_params(config: HybridConfig, key) -> Dict[str, Any]:
    """{"tok_embed", "layers": {segNN: {position in the pattern: layer
    parameters stacked on a leading repeats axis}}, "final_norm_w/_b"}."""
    k_embed, k_layers = jax.random.split(key)
    return _PARAMS.init(config, {"tok_embed": k_embed, "layers": k_layers})


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def layer_norm(x, w, b, eps):
    dtype = x.dtype
    x = x.astype(F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * w.astype(F32)
            + b.astype(F32)).astype(dtype)


_matmul = stack.matmul


def recurrence(x, dt, a_log, b_t, c_t, d, config: HybridConfig):
    """The mamba mixer's recurrence on its operands as the mixer makes them
    (x [b, s, d_inner]; dt like x, b_t and c_t [b, s, state], float32;
    `A_log` and `D` as the parameters hold them) -> y like x: the state in
    float32 (ops/selective_scan.py)."""
    from ray_tpu.ops.selective_scan import selective_scan

    return selective_scan(x.astype(config.dtype), dt,
                          -jnp.exp(a_log.astype(F32)), b_t, c_t,
                          d.astype(F32))


def _mamba_mixer(u, lp, c: HybridConfig):
    """-> (the mixer's output, the scan's output y before the gate)."""
    n, r = c.mamba_d_state, c.mamba_dt_rank
    x, z = jnp.split(_matmul(u, lp["in_proj"], c), 2, axis=-1)
    x = with_logical_constraint(x, ("batch", "seq", "ssm_inner"))
    x = jax.nn.silu(common.causal_depthwise_conv(x, lp["conv_w"],
                                                 lp["conv_b"]))
    # the step, B and C feed the float32 recurrence: fp32 out of the MXU
    proj = _matmul(x, lp["x_proj"], c, F32)
    rank, b_t, c_t = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    dt = jax.nn.softplus(_matmul(rank, lp["dt_w"], c, F32)
                         + lp["dt_b"].astype(F32))
    y = recurrence(x, dt, lp["A_log"], b_t, c_t, lp["D"], c)
    out = _matmul(y * jax.nn.silu(z), lp["out_proj"], c)
    return out, y


def _differential_attention(q, k, v, lp, lam_init, c: HybridConfig,
                            window: Optional[int]):
    """q: [b, s, heads x d]; k, v: [b, s, kv_heads x d], as their
    projections lay them -> [b, s, heads x d].  One flash_attention call:
    see the module's header.

    A pair's two heads are the halves of ONE 2d-wide block of columns, so
    the operands are made there, on [b, s, pairs x 2d] and by whole tiles
    (`common.repeat_heads`), where the kernels take them; a [b, s, heads,
    d] view of any of them is a copy of it on the TPU."""
    from ray_tpu.ops.attention import flash_attention

    b, s, _ = q.shape
    heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    pairs, rep = heads // 2, heads // kv

    def first_and_second(x):
        """[.., n x 2d], the pairs (x1 | x2) -> (x1 | 0) and (x2 | 0) of
        every pair: queries and keys zero-padded from d to 2d."""
        first = (jnp.arange(x.shape[-1]) % (2 * d)) < d
        second = jnp.pad(x[..., d:], ((0, 0), (0, 0), (0, d)))  # d to the left
        return jnp.where(first, x, 0), jnp.where(first, second, 0)

    def for_queries(x):     # one KV pair for each of its `rep` query pairs
        return common.repeat_heads(x, kv // 2, rep)

    values = for_queries(v)
    out = flash_attention(
        *(jnp.concatenate(x, axis=-1).reshape(b, s, heads, 2 * d) for x in (
            first_and_second(q),
            [for_queries(x) for x in first_and_second(k)],
            [values, values])),
        causal=True, sm_scale=1.0 / math.sqrt(d), window=window)
    out = out.reshape(b, s, heads * 2 * d)
    lam = (jnp.exp(jnp.sum(lp["lq1"].astype(F32) * lp["lk1"].astype(F32)))
           - jnp.exp(jnp.sum(lp["lq2"].astype(F32) * lp["lk2"].astype(F32)))
           + lam_init)
    a = common.by_tiles(out[..., :pairs * 2 * d].astype(F32)
                        - lam * out[..., pairs * 2 * d:].astype(F32),
                        pairs)                          # [.., pairs, 8, 2d]
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                          + c.layer_norm_eps)
    a = a * lp["subln"].astype(F32) * (1.0 - lam_init)
    return common.from_tiles(a.astype(c.dtype))


def _attention_mixer(u, lp, lam_init, c: HybridConfig, window):
    """-> (the mixer's output, (k, v) as projected, [b, s, kv_heads x d])."""
    heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    qkv = _matmul(u, lp["wqkv"], c) + lp["bqkv"].astype(c.dtype)
    q = qkv[..., :heads * d]
    k = qkv[..., heads * d:(heads + kv) * d]
    v = qkv[..., (heads + kv) * d:]
    q = with_logical_constraint(q, ("batch", "seq", "heads"))
    a = _differential_attention(q, k, v, lp, lam_init, c, window)
    return _matmul(a, lp["wo"], c) + lp["bo"].astype(c.dtype), (k, v)


def _cross_mixer(u, lp, lam_init, shared_kv, c: HybridConfig):
    q = _matmul(u, lp["wq"], c) + lp["bq"].astype(c.dtype)
    a = _differential_attention(q, *shared_kv, lp, lam_init, c, None)
    return _matmul(a, lp["wo"], c) + lp["bo"].astype(c.dtype)


# a kind of layer's mixer, with its pre-norm -> the part of the step it is
_MIXER_SCOPE = {"mamba": common.SSM, "window": common.ATTN_SLIDING,
                "full": common.ATTN_FULL, "gmu": common.GMU,
                "cross": common.ATTN_CROSS}


def _layer(x, lp, lam_init, memory, shared_kv, *, kind: str,
           c: HybridConfig):
    """One layer -> (x, what it hands on: y for mamba, (k, v) for full,
    else None)."""
    handed = None
    with jax.named_scope(_MIXER_SCOPE[kind]):
        u = layer_norm(x, lp["ln1_w"], lp["ln1_b"], c.layer_norm_eps)
        u = with_logical_constraint(u, ("batch", "seq", "embed"))
        if kind == "mamba":
            mixed, handed = _mamba_mixer(u, lp, c)
        elif kind == "window":
            mixed, _ = _attention_mixer(u, lp, lam_init, c, c.sliding_window)
        elif kind == "full":
            mixed, handed = _attention_mixer(u, lp, lam_init, c, None)
        elif kind == "gmu":
            mixed = _matmul(memory * jax.nn.silu(_matmul(u, lp["w1"], c)),
                            lp["w2"], c)
        else:
            mixed = _cross_mixer(u, lp, lam_init, shared_kv, c)
    x = with_logical_constraint(x + mixed, ("batch", "seq", "embed"))
    with jax.named_scope(common.MLP):
        y = layer_norm(x, lp["ln2_w"], lp["ln2_b"], c.layer_norm_eps)
    x = x + common.swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"], c.dtype)
    return with_logical_constraint(x, ("batch", "seq", "embed")), handed


def forward_hidden(params: Dict[str, Any], tokens, config: HybridConfig):
    """Embedding + layers + final LayerNorm: [b, s] -> ([b, s, hidden],
    None: no layer routes).  The walk is this file's own: unrolled single
    layers hand a memory and a shared KV on to the scanned pairs."""
    c = config
    x = common.embed_tokens(params["tok_embed"], tokens, c.dtype)
    memory = shared_kv = None
    for si, (pattern, first, repeats) in enumerate(segments(c)):
        seg = params["layers"][stack.segment_name(si)]
        fns = [stack.layer_fn(_layer, kind, c) for kind in pattern]
        lam = jnp.asarray(
            [[lambda_init(first + rep * len(pattern) + pos)
              for pos in range(len(pattern))] for rep in range(repeats)], F32)
        if repeats == 1:
            for pos, kind in enumerate(pattern):
                lp = jax.tree.map(lambda a: a[0], seg[str(pos)])
                x, handed = fns[pos](x, lp, lam[0, pos], memory, shared_kv)
                if first + pos == c.memory_source:
                    memory = handed
                elif kind == "full":
                    shared_kv = handed
            continue

        def body(x, xs, fns=fns, memory=memory, shared_kv=shared_kv):
            seg_slice, lam_row = xs
            for pos, fn in enumerate(fns):
                x, _ = fn(x, seg_slice[str(pos)], lam_row[pos], memory,
                          shared_kv)
            return x, None

        x, _ = jax.lax.scan(body, x, (seg, lam))
    with jax.named_scope(common.LOSS):
        return layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                          c.layer_norm_eps), None


_TAIL = stack.LossTail(forward_hidden, head="tok_embed")
forward, token_nll, loss_fn = _TAIL.forward, _TAIL.token_nll, _TAIL.loss_fn
