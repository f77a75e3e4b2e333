"""The benchmark's arithmetic for the Mamba-2 / attention / routed-expert model
whose layers are one sublayer each (NVIDIA-Nemotron-3-Nano-30B-A3B, the
`nemotron_h` form): operations and bytes from shapes.  Kept with the
yardstick (see arith.py).  `model` is a configuration file's `model` group:
the published key names, with `n_routed_experts` the experts HELD on this
chip and `router_width` the experts routed over.  Everything here is a
count; a time or a share needs a chip run.  The counts are of the WORK, the
equations at a chunk of 128, never of an implementation.

What is counted, a trained token (forward + backward = 3 x forward for a
matmul; remat's second forward is not counted):

- matmuls outside the routed experts: 6 x every weight of a matrix the
  token is multiplied by, by kind of layer: a mamba layer's W_in and W_out,
  an attention layer's W_q, W_k, W_v and W_o, an expert layer's router
  (once, whatever its float32 passes cost) and shared expert; the untied
  head once (the embedding lookup is not a matmul, nor is the depthwise
  convolution);
- the routed experts: 6 x 2 x hidden x expert width (TWO matrices: the
  expert is ungated) for each ROW routed to an expert held here.  A token
  sends `rows_per_token` rows here an expert layer: measured (the step's
  `moe_rows_held_all_layers` over its tokens and expert layers) or, with
  none given, the expectation under even routing, experts per token x held
  / router width;
- attention: 3 x the forward's operations over the (query, key) pairs of
  the causal triangle, seq (seq + 1) / 2, at 4 x head_dim a pair a query
  head.  GQA's repeat is no matmul;
- the recurrence: 3 x the forward's operations IN ITS CHUNKED FORM AT A
  CHUNK OF 128, whatever chunk a kernel uses.  A head a chunk of C steps
  over a state [P, N]: C_g B_g^T once a GROUP (2 C^2 N over the group's
  heads), the masked product with dt X (2 C^2 P), the product with the
  state C S^T (2 C N P) and the state's update B^T (..X) (2 C N P).  The
  decays, dt's scaling, D's term, the gated norm and the convolution are not
  counted.
"""

from __future__ import annotations

from typing import Dict, Optional

SCAN_CHUNK = 128
MAMBA, FULL, EXPERTS = "M", "*", "E"


def _dims(model: dict) -> dict:
    held = int(model["n_routed_experts"])
    pattern = str(model["hybrid_override_pattern"])
    heads, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    G, N = int(model["n_groups"]), int(model["ssm_state_size"])
    return {
        "h": int(model["hidden_size"]), "kinds": list(pattern),
        "H": heads, "P": P, "G": G, "N": N, "inner": heads * P,
        "conv": heads * P + 2 * G * N, "taps": int(model["conv_kernel"]),
        "heads": int(model["num_attention_heads"]),
        "kv": int(model["num_key_value_heads"]),
        "d": int(model["head_dim"]),
        "m": int(model["moe_intermediate_size"]),
        "shared_m": int(model["moe_shared_expert_intermediate_size"]),
        "held": held,
        "width": int(model.get("router_width") or held),
        "k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
    }


def layers_of(model: dict, kind: str) -> int:
    return _dims(model)["kinds"].count(kind)


def params_by_part(model: dict) -> Dict[str, int]:
    """Parameters of the kinds of layer and of the model's ends; `*_matmul`
    the part of a layer a token is multiplied by."""
    d = _dims(model)
    h = d["h"]
    mamba_matmul = h * (d["inner"] + d["conv"] + d["H"]) + d["inner"] * h
    full_matmul = 2 * h * d["heads"] * d["d"] + 2 * h * d["kv"] * d["d"]
    return {
        "mamba_matmul": mamba_matmul,
        # the conv and its bias, A_log, D, dt_bias, the gated norm, the norm
        "mamba_layer": mamba_matmul + (d["taps"] + 1) * d["conv"]
        + 3 * d["H"] + d["inner"] + h,
        "full_matmul": full_matmul,
        "full_layer": full_matmul + h,
        "router": h * d["width"],
        "shared_expert": 2 * h * d["shared_m"],
        "one_expert": 2 * h * d["m"],
        "embedding_and_head": 2 * d["vocab"] * h,
        "final_norm": h,
    }


def expert_layer_params(model: dict) -> int:
    """An expert layer: the router and its selection bias, the shared
    expert, the norm, the held experts."""
    d, p = _dims(model), params_by_part(model)
    return (p["router"] + d["width"] + p["shared_expert"] + d["h"]
            + d["held"] * p["one_expert"])


def param_count(model: dict) -> int:
    """Every parameter the train state holds."""
    p = params_by_part(model)
    a_layer = {MAMBA: p["mamba_layer"], FULL: p["full_layer"],
               EXPERTS: expert_layer_params(model)}
    return (p["embedding_and_head"] + p["final_norm"]
            + sum(a_layer[kind] for kind in _dims(model)["kinds"]))


def expected_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here, an expert layer, under
    even routing."""
    d = _dims(model)
    return d["k"] * d["held"] / d["width"]


def scan_chunk_fwd_flops(model: dict) -> float:
    """The recurrence's forward, a head a chunk of 128 (the header)."""
    d, C = _dims(model), SCAN_CHUNK
    return (2.0 * C * C * d["N"] * d["G"] / d["H"] + 2.0 * C * C * d["P"]
            + 4.0 * C * d["N"] * d["P"])


def scan_fwd_flops(rows: float, model: dict, seq: int) -> float:
    """One mamba layer's recurrence forward over `rows` sequences of `seq`:
    whole chunks of 128 a head."""
    chunks = -(-seq // SCAN_CHUNK)
    return rows * _dims(model)["H"] * chunks * scan_chunk_fwd_flops(model)


def scan_min_bytes(rows: float, model: dict, seq: int,
                   operand_bytes: int = 2) -> float:
    """The least one call of the recurrence's forward must move: x in and y
    out once a head, B and C once a GROUP (`operand_bytes` each, the compute
    dtype's), dt once a head in float32."""
    d = _dims(model)
    return rows * seq * (
        operand_bytes * (2 * d["inner"] + 2 * d["G"] * d["N"]) + 4 * d["H"])


def visible_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def attention_fwd_flops(rows: float, model: dict, seq: int) -> float:
    """One attention layer's forward: 4 x head_dim a pair of the triangle a
    query head."""
    d = _dims(model)
    return 4.0 * d["d"] * d["heads"] * rows * visible_pairs(seq)


def train_flops_per_token(model: dict, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Forward + backward operations a trained token requires (the
    header's four parts)."""
    d, p = _dims(model), params_by_part(model)
    if rows_per_token is None:
        rows_per_token = expected_rows_per_token(model)
    mamba, full, experts = (layers_of(model, k)
                            for k in (MAMBA, FULL, EXPERTS))
    matmul_weights = (mamba * p["mamba_matmul"] + full * p["full_matmul"]
                      + experts * (p["router"] + p["shared_expert"])
                      + d["vocab"] * d["h"])
    routed = experts * rows_per_token * p["one_expert"]
    attn = full * 3.0 * attention_fwd_flops(1.0, model, seq_len) / seq_len
    scan = mamba * 3.0 * scan_fwd_flops(1.0, model, seq_len) / seq_len
    return 6.0 * (matmul_weights + routed) + attn + scan
