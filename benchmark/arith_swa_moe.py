"""The benchmark's arithmetic for the windowed / full grouped-query model
with per-head gates and softmax-routed experts (Laguna-S-2.1, the `laguna`
form): operations and bytes from shapes.  Kept with the yardstick (see
arith.py).  `model` is a configuration file's `model` group: the published
key names, with `num_experts` the experts HELD on this chip and
`router_width` the experts routed over; the per-layer lists may be the
published whole ones, of which the first `num_hidden_layers` entries count.
Everything here is a count; a time or a share needs a chip run.

What is counted, a trained token (forward + backward = 3 x forward for a
matmul; remat's second forward is not counted):

- matmuls outside the routed experts: 6 x every weight of a matrix the
  token is multiplied by, by kind of layer: W_q, W_o and the gate's W_g by
  the layer's query heads, W_k and W_v by the KV heads, the dense layers'
  SwiGLU, an expert layer's router and shared expert, the untied head once
  (the embedding lookup is not a matmul);
- the routed experts: 6 x 3 x hidden x expert width for each ROW routed to
  an expert held here.  A token sends `rows_per_token` rows here: measured
  (the step's `moe_rows_held_all_layers` over its tokens and expert layers)
  or, with none given, the expectation under even routing, experts per
  token x held / router width.  The other chips' experts' rows are theirs;
- attention: 3 x the forward's operations over the (query, key) pairs a
  query may SEE (`arith_hybrid.visible_pairs`): seq (seq + 1) / 2 in a full
  layer, w (w + 1) / 2 + (seq - w) w under a window of w; a pair costs q k^T and p V over the head size,
  4 x head_dim a query head.  GQA's repeat of k and v, rope and the gate's
  elementwise product are no matmuls and are not counted.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.arith_hybrid import visible_pairs

FULL, SLIDING = "full_attention", "sliding_attention"


def _dims(model: dict) -> dict:
    n = int(model["num_hidden_layers"])
    held = int(model["num_experts"])
    heads = model.get("num_attention_heads_per_layer") \
        or [model["num_attention_heads"]] * n
    dense = [int(i) for i in model["mlp_only_layers"] if int(i) < n]
    return {
        "h": int(model["hidden_size"]), "d": int(model["head_dim"]),
        "kv": int(model["num_key_value_heads"]),
        "heads": [int(x) for x in heads[:n]],
        "kinds": list(model["layer_types"][:n]),
        "window": int(model["sliding_window"]),
        "dense_m": int(model["intermediate_size"]),
        "m": int(model["moe_intermediate_size"]),
        "shared_m": int(model["shared_expert_intermediate_size"]),
        "held": held,
        "width": int(model.get("router_width") or held),
        "k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
        "layers": n, "dense_layers": dense,
    }


def expert_layers(model: dict) -> int:
    d = _dims(model)
    return d["layers"] - len(d["dense_layers"])


def heads_by_kind(model: dict) -> Dict[str, List[int]]:
    """The query heads of every layer of each kind of attention."""
    d = _dims(model)
    return {kind: [h for k, h in zip(d["kinds"], d["heads"]) if k == kind]
            for kind in (FULL, SLIDING)}


def attention_params(model: dict, heads: int) -> int:
    """W_q, W_k, W_v, the gate's W_g and W_o of a layer of `heads`."""
    d = _dims(model)
    return (d["h"] * heads * d["d"] + 2 * d["h"] * d["kv"] * d["d"]
            + d["h"] * heads + heads * d["d"] * d["h"])


def params_by_kind(model: dict) -> Dict[str, int]:
    """Parameters of the parts a layer may have and of the model's ends."""
    d = _dims(model)
    h = d["h"]
    return {
        "norms": 2 * h,
        "dense_ffn": 3 * h * d["dense_m"],
        "router": h * d["width"],
        "shared_expert": 3 * h * d["shared_m"],
        "one_expert": 3 * h * d["m"],
        "embedding_and_head": 2 * d["vocab"] * h,
        "final_norm": h,
    }


def param_count(model: dict) -> int:
    """Every parameter the train state holds."""
    d, p = _dims(model), params_by_kind(model)
    total = p["embedding_and_head"] + p["final_norm"]
    for layer, heads in enumerate(d["heads"]):
        total += attention_params(model, heads) + p["norms"]
        total += p["dense_ffn"] if layer in d["dense_layers"] else (
            p["router"] + p["shared_expert"] + d["held"] * p["one_expert"])
    return total


def expected_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here under even routing."""
    d = _dims(model)
    return d["k"] * d["held"] / d["width"]


def attention_fwd_flops(rows: float, heads: int, head_dim: int, seq: int,
                        window=None) -> float:
    """Operations one layer's attention needs forward: 4 x head_dim a
    visible pair a query head."""
    return 4.0 * head_dim * heads * rows * visible_pairs(seq, window)


def kind_fwd_flops(rows: float, model: dict, seq: int, kind: str) -> float:
    """`attention_fwd_flops` of ALL the layers of one kind together."""
    d = _dims(model)
    return sum(attention_fwd_flops(rows, heads, d["d"], seq,
                                   d["window"] if kind == SLIDING else None)
               for heads in heads_by_kind(model)[kind])


def train_flops_per_token(model: dict, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Forward + backward operations a trained token requires (the
    header's three parts)."""
    d, p = _dims(model), params_by_kind(model)
    if rows_per_token is None:
        rows_per_token = expected_rows_per_token(model)
    sparse = expert_layers(model)
    matmul_weights = (
        sum(attention_params(model, heads) for heads in d["heads"])
        + len(d["dense_layers"]) * p["dense_ffn"]
        + sparse * (p["router"] + p["shared_expert"])
        + d["vocab"] * d["h"])
    routed = sparse * rows_per_token * p["one_expert"]
    attn = sum(3.0 * kind_fwd_flops(1.0, model, seq_len, kind) / seq_len
               for kind in (FULL, SLIDING))
    return 6.0 * (matmul_weights + routed) + attn
