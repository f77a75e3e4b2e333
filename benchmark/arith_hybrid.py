"""The benchmark's arithmetic for the hybrid decoder-decoder model
(Phi-4-mini-flash-reasoning): operations and bytes from shapes.  Kept with
the yardstick (see arith.py).  `model` is a configuration file's `model`
group: the published key names + `layer_kinds` and the `mamba_*` sizes.
Everything here is a count; a time or a share needs a chip run.

What is counted, a trained token (forward + backward = 3 x forward for a
matmul: one product forward, two backward; remat's second forward is not
counted):

- matmuls: 6 x every weight of a matrix the token is multiplied by, by
  kind of layer (`mixer_matmul_params`), the feed-forward's three matrices
  and the tied head once (the embedding lookup is not a matmul);
- attention: 3 x the forward's operations over the (query, key) pairs a
  query may actually SEE: seq (seq + 1) / 2 under the causal mask (full
  and cross layers), w (w + 1) / 2 + (seq - w) w under a window of w.
  Differential attention has one softmax map a query head (two a pair);
  a map's forward is q k^T over head_dim (2 x head_dim operations a pair)
  and p V over values of twice the head size (2 x 2 head_dim): 6 x
  head_dim a visible pair a head.  The zero padding of q and k to the
  values' width, which the program's kernel call multiplies through, is
  not needed work and is not counted;
- the scan: elementwise operations over d_inner x d_state.  Forward, an
  element a step: dt A (1), exp (1), decay x h (1), dt x B (1, the product
  dt x is shared by the states), the sum (1), h x C (1), into y (1) = 7;
  backward counted as twice that, as for a matmul.  These run on the
  vector unit, not the MXU; they are 0.1 % of the total and are in it so
  that the total is the model's, not the matmuls'.
"""

from __future__ import annotations

from typing import Dict

SCAN_FWD_OPS_PER_ELEMENT = 7


def _dims(model: dict) -> dict:
    h = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    return {
        "h": h, "heads": heads, "kv": int(model["num_key_value_heads"]),
        "hd": h // heads, "m": int(model["intermediate_size"]),
        "vocab": int(model["vocab_size"]),
        "window": int(model["sliding_window"]),
        "di": int(model["mamba_expand"]) * h,
        "n": int(model["mamba_d_state"]), "r": int(model["mamba_dt_rank"]),
        "taps": int(model["mamba_d_conv"]),
        "kinds": list(model["layer_kinds"]),
    }


def mixer_matmul_params(model: dict) -> Dict[str, int]:
    """Weights of the matrices a token is multiplied by in each kind of
    mixer (biases, norms, the conv and the scan's A, D are no matmuls)."""
    d = _dims(model)
    h, di, hd = d["h"], d["di"], d["hd"]
    out = d["heads"] * hd * h
    return {
        "mamba": h * 2 * di + di * (d["r"] + 2 * d["n"]) + d["r"] * di
                 + di * h,
        "window": h * (d["heads"] + 2 * d["kv"]) * hd + out,
        "full": h * (d["heads"] + 2 * d["kv"]) * hd + out,
        "gmu": h * di + di * h,
        "cross": h * d["heads"] * hd + out,
    }


def mixer_other_params(model: dict) -> Dict[str, int]:
    """The parameters of a mixer that are in no matmul."""
    d = _dims(model)
    h, di, hd = d["h"], d["di"], d["hd"]
    attn = h + 4 * hd + 2 * hd          # output bias, lambda vectors, norm
    return {
        "mamba": d["taps"] * di + di + di + di * d["n"] + di,
        "window": (d["heads"] + 2 * d["kv"]) * hd + attn,
        "full": (d["heads"] + 2 * d["kv"]) * hd + attn,
        "gmu": 0,
        "cross": d["heads"] * hd + attn,
    }


def param_count(model: dict) -> int:
    """Every parameter, the tied matrix once: what the train state holds."""
    d = _dims(model)
    mats, rest = mixer_matmul_params(model), mixer_other_params(model)
    per_layer = 3 * d["h"] * d["m"] + 4 * d["h"]     # MLP + two LayerNorms
    return (d["vocab"] * d["h"] + 2 * d["h"]
            + sum(mats[k] + rest[k] + per_layer for k in d["kinds"]))


def visible_pairs(seq: int, window=None) -> float:
    """(query, key) pairs of one sequence a query may see."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * float(window)


def attention_fwd_flops(rows: float, heads: int, head_dim: int, seq: int,
                        window=None) -> float:
    """Operations one layer's differential attention needs forward: 6 x
    head_dim a visible pair a query head (the header says why)."""
    return 6.0 * head_dim * heads * rows * visible_pairs(seq, window)


def scan_elements_per_token(model: dict) -> int:
    d = _dims(model)
    return d["di"] * d["n"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward operations a trained token requires (the
    header's three parts)."""
    d = _dims(model)
    mats = mixer_matmul_params(model)
    matmul = 6.0 * (d["vocab"] * d["h"] + sum(
        mats[k] + 3 * d["h"] * d["m"] for k in d["kinds"]))
    attn = 0.0
    for k in d["kinds"]:
        if k in ("window", "full", "cross"):
            attn += 3.0 * attention_fwd_flops(
                1.0, d["heads"], d["hd"], seq_len,
                d["window"] if k == "window" else None) / seq_len
    scan = (3.0 * SCAN_FWD_OPS_PER_ELEMENT * scan_elements_per_token(model)
            * d["kinds"].count("mamba"))
    return matmul + attn + scan


def scan_min_bytes(rows: float, seq: int, model: dict) -> float:
    """The least bytes ONE forward call of the scan kernel must move: read
    x (the model's bfloat16) and dt (float32: it feeds the exponent)
    [tokens, d_inner], B and C [tokens, d_state] float32, A [d_inner,
    d_state] and D [d_inner] float32; write y [tokens, d_inner] bfloat16.
    The gate z is outside the kernel.  What the kernel moves beyond this
    (float32 x and y, saved states) is not needed and not counted."""
    d = _dims(model)
    tokens = rows * seq
    return (tokens * d["di"] * (2 + 4 + 2) + 2 * tokens * d["n"] * 4
            + d["di"] * d["n"] * 4 + d["di"] * 4)
