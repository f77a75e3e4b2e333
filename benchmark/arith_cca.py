"""The benchmark's arithmetic for the compressed-convolutional-attention model
with a top-1 expert layer behind an MLP router (ZAYA1-8B, the `zaya` form):
operations and bytes from shapes.  Kept with the yardstick (see arith.py).
`model` is a configuration file's `model` group: the published key names,
with `num_experts` the experts HELD on this chip and `router_width` the
experts routed over.  Everything here is a count; a time or a share needs a
chip run.

What is counted, a trained token (forward + backward = 3 x forward for a
matmul; remat's second forward is not counted):

- matmuls outside the routed experts: 6 x every weight of a matrix the
  token is multiplied by: a layer's W_q, W_k, W_v1, W_v2 and W_o (the latents
  L_q = heads x d and L_kv = KV heads x d), the head-mixing convolution's
  d x d matrix a tap a head, and the router's four matrices (W_rd, W_1, W_2,
  W_3; they run in float32 at full precision, several MXU passes a product:
  counted once, as the algorithm's); the TIED head once (the embedding lookup
  is not a matmul, nor is the depthwise convolution);
- the routed experts: 6 x 3 x hidden x expert width for each ROW routed to
  an expert held here.  A token sends `rows_per_token` rows here: measured
  (the step's `moe_rows_held_all_layers` over its tokens and layers) or,
  with none given, the expectation under even routing, experts per token x
  held / router width (1 with all sixteen held, whatever the routing);
- attention: 3 x the forward's operations over the (query, key) pairs of
  the causal triangle, seq (seq + 1) / 2, at 4 x head_dim a pair a query
  head.  GQA's repeat, the shift, the depthwise convolution, the q-k mean,
  the l2 norm, rope and the residual scaling are no matmuls and are not
  counted.
"""

from __future__ import annotations

from typing import Dict, Optional


def _dims(model: dict) -> dict:
    held = int(model["num_experts"])
    heads, kv = (int(model["num_attention_heads"]),
                 int(model["num_key_value_heads"]))
    d = int(model["head_dim"])
    return {
        "h": int(model["hidden_size"]),
        "layers": int(model["num_hidden_layers"]),
        "heads": heads, "kv": kv, "d": d,
        "lq": heads * d, "lkv": kv * d,
        "taps0": int(model["cca_time0"]), "taps1": int(model["cca_time1"]),
        "r": int(model["router_hidden_size"]),
        "m": int(model["moe_intermediate_size"]),
        "held": held,
        "width": int(model.get("router_width") or held),
        "k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
    }


def params_by_part(model: dict) -> Dict[str, int]:
    """Parameters of ONE layer's parts and of the model's ends; `*_matmul`
    the part of it a token is multiplied by."""
    d = _dims(model)
    h, r, channels = d["h"], d["r"], d["lq"] + d["lkv"]
    attention_matmul = 2 * h * d["lq"] + 2 * h * d["lkv"]   # q, o; k, v1 + v2
    head_mix = (d["heads"] + d["kv"]) * d["taps1"] * d["d"] * d["d"]
    router_matmul = h * r + 2 * r * r + r * d["width"]
    return {
        "attention_matmul": attention_matmul,
        "depthwise_conv": d["taps0"] * channels + channels,
        "head_mix_matmul": head_mix,
        "head_mix_conv": head_mix + channels,
        "tau": d["kv"],
        "router_matmul": router_matmul,
        # the biases of W_rd, W_1, W_2; the carry's alpha; the norm; beta
        "router": router_matmul + 3 * r + r + r + d["width"],
        "one_expert": 3 * h * d["m"],
        "norms": 2 * h,
        "residual_scales": 8 * h,
        "embedding_and_head": d["vocab"] * h,       # tied: one table
        "final_norm": h,
    }


def layer_params(model: dict) -> int:
    d, p = _dims(model), params_by_part(model)
    return (p["attention_matmul"] + p["depthwise_conv"] + p["head_mix_conv"]
            + p["tau"] + p["router"] + d["held"] * p["one_expert"]
            + p["norms"] + p["residual_scales"])


def param_count(model: dict) -> int:
    """Every parameter the train state holds."""
    p = params_by_part(model)
    return (_dims(model)["layers"] * layer_params(model)
            + p["embedding_and_head"] + p["final_norm"])


def expected_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here under even routing."""
    d = _dims(model)
    return d["k"] * d["held"] / d["width"]


def visible_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def attention_fwd_flops(rows: float, model: dict, seq: int) -> float:
    """One layer's attention forward: 4 x head_dim a pair of the triangle a
    query head."""
    d = _dims(model)
    return 4.0 * d["d"] * d["heads"] * rows * visible_pairs(seq)


def layer_fwd_flops_per_token(model: dict, seq: int,
                              rows_per_token: Optional[float] = None
                              ) -> Dict[str, float]:
    """A layer's forward operations a token, by part (the cell's `why`)."""
    p = params_by_part(model)
    if rows_per_token is None:
        rows_per_token = expected_rows_per_token(model)
    return {
        "projections": 2.0 * p["attention_matmul"],
        "head_mix_conv": 2.0 * p["head_mix_matmul"],
        "triangle": attention_fwd_flops(1.0, model, seq) / seq,
        "router": 2.0 * p["router_matmul"],
        "experts": 2.0 * rows_per_token * p["one_expert"],
    }


def train_flops_per_token(model: dict, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Forward + backward operations a trained token requires (the
    header's three parts)."""
    d = _dims(model)
    layer = layer_fwd_flops_per_token(model, seq_len, rows_per_token)
    head = 2.0 * d["vocab"] * d["h"]
    return 3.0 * (d["layers"] * sum(layer.values()) + head)
