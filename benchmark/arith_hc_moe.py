"""The benchmark's arithmetic for the hyper-connected latent-attention,
routed-expert model with a multi-token-prediction block (Xing4.0-29B-A4B):
operations and bytes from shapes, on top of arith_moe.py's (whose router,
experts, attention pairs and grouped-matmul counts hold here unchanged).
Kept with the yardstick (see arith.py).  `model` is a configuration file's
`model` group.  Everything here is a count; a time or a share needs a chip
run.

What is counted, a trained token (forward + backward = 3 x forward for a
matmul; remat's second forward is not counted):

- arith_moe.py's three parts, with the query's projection as its two
  matrices W_qa [h, q_lora_rank] and W_qb [q_lora_rank, heads x 192];
- the lanes: a sublayer's product of the stream with w_hc, n h x (2 n + n^2)
  weights, two sublayers a layer, and the collapse's n h x n behind the
  stack.  The lanes' weighted sums (n and n^2 multiply-adds a column) are
  the vector unit's and are not counted, as no norm or SiLU is;
- the prediction block, where the configuration has one: W_eh [2 h, h], ONE
  expert layer of the model's kind (its attention over the same pairs, its
  lanes, router, shared expert and its routed rows, taken as an expert
  layer's of the stack), its collapse, and the head A SECOND TIME.

The lanes' kernels move the stream and little else; their least bytes a
call are below (`hc_*_min_bytes`).
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import arith_moe


def _lanes(model: dict) -> int:
    return int(model.get("hc_mult") or 0)


def _mix_width(model: dict) -> int:
    n = _lanes(model)
    return 2 * n + n * n


def params_by_kind(model: dict) -> Dict[str, int]:
    """Parameters of ONE layer's parts and of the model's ends, by kind:
    arith_moe.py's, the attention with the query's latent, and the lanes'
    leaves (`lanes`: both sublayers' of a layer; `collapse`)."""
    d = arith_moe._dims(model)
    out = arith_moe.params_by_kind(model)
    h, n, q_rank = d["h"], _lanes(model), int(model.get("q_lora_rank") or 0)
    if q_rank:
        qk = d["heads"] * (d["nope"] + d["rope"])
        out["attention"] += h * q_rank + q_rank + q_rank * qk - h * qk
    width = _mix_width(model)
    out["lanes"] = 2 * (n * h * width + 3 + width) if n else 0
    out["collapse"] = n * h * n + 1 + n if n else 0
    out["mtp_in"] = 2 * h * h + 2 * h
    return out


def _layers(model: dict):
    """(parameters of a dense layer, of an expert layer)."""
    d, p = arith_moe._dims(model), params_by_kind(model)
    common = p["attention"] + p["norms"] + p["lanes"]
    return (common + p["dense_ffn"],
            common + p["router"] + p["shared_expert"]
            + d["held"] * p["one_expert"])


def param_count(model: dict) -> int:
    """Every parameter the train state holds."""
    d, p = arith_moe._dims(model), params_by_kind(model)
    dense, expert = _layers(model)
    behind = p["final_norm"] + p["collapse"]
    total = (p["embedding_and_head"] + behind + d["dense_layers"] * dense
             + (d["layers"] - d["dense_layers"]) * expert)
    if int(model.get("num_nextn_predict_layers") or 0):
        total += p["mtp_in"] + expert + behind
    return total


def train_flops_per_token(model: dict, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Forward + backward operations a trained token requires (the
    header's parts)."""
    d, p = arith_moe._dims(model), params_by_kind(model)
    if rows_per_token is None:
        rows_per_token = arith_moe.expected_rows_per_token(model)
    h, n, q_rank = d["h"], _lanes(model), int(model.get("q_lora_rank") or 0)
    mtp = 1 if int(model.get("num_nextn_predict_layers") or 0) else 0
    # less the norms' weights: the latent's, and the query latent's
    attention = p["attention"] - d["rank"] - q_rank
    lanes = 2 * n * h * _mix_width(model)
    expert_layer = (attention + lanes + h * d["width"] + p["shared_expert"]
                    + rows_per_token * p["one_expert"])
    dense_layer = attention + lanes + p["dense_ffn"]
    head, collapse = d["vocab"] * h, n * h * n
    weights = (d["dense_layers"] * dense_layer
               + (d["layers"] - d["dense_layers"] + mtp) * expert_layer
               + (1 + mtp) * (head + collapse) + mtp * 2 * h * h)
    attn = (d["layers"] + mtp) * 3.0 * arith_moe.attention_fwd_flops(
        1.0, model, seq_len) / seq_len
    return 6.0 * weights + attn


def hc_pre_fwd_min_bytes(tokens: float, model: dict, itemsize: int = 2
                         ) -> float:
    """The least bytes one `hc_pre_fwd` call must move: the stream in, the
    lanes' weighted sum out, a token's 2 n + n^2 float32 numbers out, and
    the float32 leaf once."""
    h, n, width = int(model["hidden_size"]), _lanes(model), _mix_width(model)
    return tokens * (itemsize * (n * h + h) + 4 * width) + 4 * n * h * width


def hc_post_fwd_min_bytes(tokens: float, model: dict, itemsize: int = 2
                          ) -> float:
    """.. and one `hc_post_fwd` call: the stream in and out, the sublayer's
    result in, a token's n + n^2 float32 numbers in."""
    h, n = int(model["hidden_size"]), _lanes(model)
    return tokens * (itemsize * (2 * n * h + h) + 4 * (n + n * n))
