"""The benchmark's arithmetic for the latent-attention, routed-expert model
(Kanana-2-30B-A3B, the DeepSeek-V3 form): operations and bytes from shapes.
Kept with the yardstick (see arith.py).  `model` is a configuration file's
`model` group: the published key names, with `n_routed_experts` the experts
HELD on this chip and `router_width` the experts routed over.  Everything
here is a count; a time or a share needs a chip run.

What is counted, a trained token (forward + backward = 3 x forward for a
matmul; remat's second forward is not counted):

- matmuls outside the routed experts: 6 x every weight of a matrix the
  token is multiplied by: the latent projections (W_q, W_kva, W_kvb, W_o),
  the dense layers' SwiGLU, an expert layer's router and shared expert, the
  untied head once (the embedding lookup is not a matmul);
- the routed experts: 6 x 3 x hidden x expert width for each ROW routed to
  an expert held here.  A token sends `rows_per_token` rows here: measured
  (the step's `moe_rows_held` over its tokens) or, with none given, the
  expectation under even routing, experts per token x held / router width.
  The rows of the other chips' experts are the other chips' work;
- attention: 3 x the forward's operations over the (query, key) pairs a
  query may SEE, seq (seq + 1) / 2: a pair costs q k^T over the keys'
  width (2 x 192) and p V over the values' (2 x 128) a head, 2 x 320.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence


def _dims(model: dict) -> dict:
    held = int(model["n_routed_experts"])
    return {
        "h": int(model["hidden_size"]),
        "heads": int(model["num_attention_heads"]),
        "nope": int(model["qk_nope_head_dim"]),
        "rope": int(model["qk_rope_head_dim"]),
        "v": int(model["v_head_dim"]),
        "rank": int(model["kv_lora_rank"]),
        "dense_m": int(model["intermediate_size"]),
        "m": int(model["moe_intermediate_size"]),
        "shared": int(model["n_shared_experts"]),
        "held": held,
        "width": int(model.get("router_width") or held),
        "k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
        "layers": int(model["num_hidden_layers"]),
        "dense_layers": int(model["first_k_dense_replace"]),
    }


def params_by_kind(model: dict) -> Dict[str, int]:
    """Parameters of ONE layer's parts and of the model's ends, by kind."""
    d = _dims(model)
    h = d["h"]
    return {
        "attention": (h * d["heads"] * (d["nope"] + d["rope"])
                      + h * (d["rank"] + d["rope"]) + d["rank"]
                      + d["rank"] * d["heads"] * (d["nope"] + d["v"])
                      + d["heads"] * d["v"] * h),
        "norms": 2 * h,
        "dense_ffn": 3 * h * d["dense_m"],
        "router": h * d["width"] + d["width"],
        "shared_expert": 3 * h * d["shared"] * d["m"],
        "one_expert": 3 * h * d["m"],
        "embedding_and_head": 2 * d["vocab"] * h,
        "final_norm": h,
    }


def param_count(model: dict) -> int:
    """Every parameter the train state holds."""
    d, p = _dims(model), params_by_kind(model)
    common = p["attention"] + p["norms"]
    expert_layer = (common + p["router"] + p["shared_expert"]
                    + d["held"] * p["one_expert"])
    return (p["embedding_and_head"] + p["final_norm"]
            + d["dense_layers"] * (common + p["dense_ffn"])
            + (d["layers"] - d["dense_layers"]) * expert_layer)


def expected_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here under even routing."""
    d = _dims(model)
    return d["k"] * d["held"] / d["width"]


def visible_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2


def attention_fwd_flops(rows: float, model: dict, seq: int) -> float:
    """Operations one layer's attention needs forward: 2 x (keys' width +
    values' width) a visible pair a head."""
    d = _dims(model)
    return (2.0 * (d["nope"] + d["rope"] + d["v"]) * d["heads"] * rows
            * visible_pairs(seq))


def train_flops_per_token(model: dict, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Forward + backward operations a trained token requires (the
    header's three parts)."""
    d, p = _dims(model), params_by_kind(model)
    if rows_per_token is None:
        rows_per_token = expected_rows_per_token(model)
    expert_layers = d["layers"] - d["dense_layers"]
    matmul_weights = (
        d["layers"] * (p["attention"] - d["rank"])      # less the latent norm
        + d["dense_layers"] * p["dense_ffn"]
        + expert_layers * (d["h"] * d["width"] + p["shared_expert"])
        + d["vocab"] * d["h"])
    routed = expert_layers * rows_per_token * p["one_expert"]
    attn = d["layers"] * 3.0 * attention_fwd_flops(1.0, model, seq_len) \
        / seq_len
    return 6.0 * (matmul_weights + routed) + attn


def grouped_matmul_flops(group_sizes: Sequence[float], k: int, n: int
                         ) -> float:
    """Operations of out[rows of g] = x[rows of g] @ w[g] [k, n]: two a
    multiply-add a row."""
    return 2.0 * float(sum(group_sizes)) * k * n


def grouped_matmul_min_bytes(group_sizes: Sequence[float], k: int, n: int,
                             itemsize: int = 2) -> float:
    """The least bytes one such call must move: every present row of x in
    and of the result out, and the matrix of every group that has a row,
    once.  Padding rows and the matrices of empty groups are not needed."""
    rows = float(sum(group_sizes))
    used = sum(1 for s in group_sizes if s > 0)
    return itemsize * (rows * (k + n) + used * k * n)
