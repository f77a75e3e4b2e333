"""The benchmark's arithmetic for the gated-delta-rule / gated-full-attention
model with softmax-routed experts and a gated shared expert (Qwen3-Next-80B-
A3B, the `qwen3_next` form): operations and bytes from shapes.  Kept with the
yardstick (see arith.py).  `model` is a configuration file's `model` group:
the published key names, with `num_experts` the experts HELD on this chip and
`router_width` the experts routed over.  Everything here is a count; a time
or a share needs a chip run.

What is counted, a trained token (forward + backward = 3 x forward for a
matmul; remat's second forward is not counted):

- matmuls outside the routed experts: 6 x every weight of a matrix the
  token is multiplied by, by kind of layer: a linear layer's W_qkvz, W_ba
  and W_o; a full layer's W_q (query AND gate columns), W_k, W_v and W_o;
  every layer's router, shared expert and the shared expert's gate; the
  untied head once (the embedding lookup is not a matmul, nor is the
  depthwise convolution);
- the routed experts: 6 x 3 x hidden x expert width for each ROW routed to
  an expert held here.  A token sends `rows_per_token` rows here: measured
  (the step's `moe_rows_held_all_layers` over its tokens and layers) or,
  with none given, the expectation under even routing, experts per token x
  held / router width;
- full attention: 3 x the forward's operations over the (query, key) pairs
  of the causal triangle, seq (seq + 1) / 2, at 4 x head_dim a pair a query
  head.  GQA's repeat, the q / k norms, rope and the gate's elementwise
  product are no matmuls and are not counted;
- the gated delta rule: 3 x the forward's operations IN ITS CHUNKED FORM AT
  A CHUNK OF 64, whatever chunk a kernel uses, so that the yardstick does
  not move with the implementation.  A value head a chunk of C steps: K K^T
  (2 C^2 d_k), the two products with the inverse ((I + A)^-1 on beta V and
  on beta exp(G) K: 2 C^2 (d_v + d_k)), Q K^T (2 C^2 d_k), the masked
  product with the new values (2 C^2 d_v) and the three products with the
  state (W S, Q S, K^T D: 6 C d_k d_v) = 2 C^2 (3 d_k + 2 d_v) + 6 C d_k
  d_v.  The inverse itself, the decays and the norms are not counted.
"""

from __future__ import annotations

from typing import Dict, Optional

RULE_CHUNK = 64
FULL, LINEAR = "full_attention", "linear_attention"


def _dims(model: dict) -> dict:
    n = int(model["num_hidden_layers"])
    every = int(model["full_attention_interval"])
    held = int(model["num_experts"])
    return {
        "h": int(model["hidden_size"]), "layers": n,
        "kinds": [FULL if (i + 1) % every == 0 else LINEAR
                  for i in range(n)],
        "heads": int(model["num_attention_heads"]),
        "kv": int(model["num_key_value_heads"]),
        "d": int(model["head_dim"]),
        "hk": int(model["linear_num_key_heads"]),
        "hv": int(model["linear_num_value_heads"]),
        "dk": int(model["linear_key_head_dim"]),
        "dv": int(model["linear_value_head_dim"]),
        "taps": int(model["linear_conv_kernel_dim"]),
        "m": int(model["moe_intermediate_size"]),
        "shared_m": int(model["shared_expert_intermediate_size"]),
        "held": held,
        "width": int(model.get("router_width") or held),
        "k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
    }


def layers_of(model: dict, kind: str) -> int:
    return _dims(model)["kinds"].count(kind)


def params_by_part(model: dict) -> Dict[str, int]:
    """Parameters of the parts a layer may have and of the model's ends;
    `*_matmul` the part of a mixer a token is multiplied by."""
    d = _dims(model)
    h = d["h"]
    conv = 2 * d["hk"] * d["dk"] + d["hv"] * d["dv"]
    linear_matmul = (h * (conv + d["hv"] * d["dv"]) + h * 2 * d["hv"]
                     + d["hv"] * d["dv"] * h)
    full_matmul = (h * d["heads"] * 2 * d["d"] + 2 * h * d["kv"] * d["d"]
                   + d["heads"] * d["d"] * h)
    return {
        "linear_matmul": linear_matmul,
        "linear_mixer": linear_matmul + d["taps"] * conv + 2 * d["hv"]
        + d["dv"],
        "full_matmul": full_matmul,
        "full_mixer": full_matmul + 2 * d["d"],
        "norms": 2 * h,
        "router": h * d["width"],
        "shared_expert": 3 * h * d["shared_m"],
        "shared_gate": h,
        "one_expert": 3 * h * d["m"],
        "embedding_and_head": 2 * d["vocab"] * h,
        "final_norm": h,
    }


def expert_layer_params(model: dict) -> int:
    d, p = _dims(model), params_by_part(model)
    return (p["router"] + p["shared_expert"] + p["shared_gate"]
            + d["held"] * p["one_expert"])


def param_count(model: dict) -> int:
    """Every parameter the train state holds."""
    p = params_by_part(model)
    total = p["embedding_and_head"] + p["final_norm"]
    for kind in _dims(model)["kinds"]:
        total += p["full_mixer" if kind == FULL else "linear_mixer"]
        total += p["norms"] + expert_layer_params(model)
    return total


def expected_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here under even routing."""
    d = _dims(model)
    return d["k"] * d["held"] / d["width"]


def rule_chunk_fwd_flops(model: dict) -> float:
    """The rule's forward, a value head a chunk of 64 (the header)."""
    d, C = _dims(model), RULE_CHUNK
    return (2.0 * C * C * (3 * d["dk"] + 2 * d["dv"])
            + 6.0 * C * d["dk"] * d["dv"])


def rule_fwd_flops(rows: float, model: dict, seq: int) -> float:
    """One linear layer's rule forward over `rows` sequences of `seq`:
    whole chunks of 64 a value head."""
    chunks = -(-seq // RULE_CHUNK)
    return rows * _dims(model)["hv"] * chunks * rule_chunk_fwd_flops(model)


def rule_min_bytes(rows: float, model: dict, seq: int,
                   operand_bytes: int = 2) -> float:
    """The least one call of the rule's forward must move: q and k once a
    KEY head, v in and o out once a value head (`operand_bytes` each, the
    compute dtype's), g and beta once a value head in float32."""
    d = _dims(model)
    return rows * seq * (
        operand_bytes * (2 * d["hk"] * d["dk"] + 2 * d["hv"] * d["dv"])
        + 4 * 2 * d["hv"])


def visible_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def attention_fwd_flops(rows: float, model: dict, seq: int) -> float:
    """One full layer's attention forward: 4 x head_dim a pair of the
    triangle a query head."""
    d = _dims(model)
    return 4.0 * d["d"] * d["heads"] * rows * visible_pairs(seq)


def train_flops_per_token(model: dict, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Forward + backward operations a trained token requires (the
    header's four parts)."""
    d, p = _dims(model), params_by_part(model)
    if rows_per_token is None:
        rows_per_token = expected_rows_per_token(model)
    linear, full = layers_of(model, LINEAR), layers_of(model, FULL)
    matmul_weights = (
        linear * p["linear_matmul"] + full * p["full_matmul"]
        + d["layers"] * (p["router"] + p["shared_expert"] + p["shared_gate"])
        + d["vocab"] * d["h"])
    routed = d["layers"] * rows_per_token * p["one_expert"]
    attn = full * 3.0 * attention_fwd_flops(1.0, model, seq_len) / seq_len
    rule = linear * 3.0 * rule_fwd_flops(1.0, model, seq_len) / seq_len
    return 6.0 * (matmul_weights + routed) + attn + rule
