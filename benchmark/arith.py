"""The benchmark's own arithmetic: operations and bytes from shapes.

Kept with the yardstick, not with the program, so that no later PR that
claims a gain can move it.  `model` is a configuration file's `model`
group (published key names).  Everything here is a count; a time or a
share needs a chip run.
"""

from __future__ import annotations

import json
import os
from typing import Iterable

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The peak rates of a known device kind.  An unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peak rates recorded for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def _dims(model: dict):
    h = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    kv = int(model.get("num_key_value_heads", heads))
    hd = int(model.get("head_dim") or h // heads)
    return (h, heads, kv, hd, int(model["intermediate_size"]),
            int(model["num_hidden_layers"]), int(model["vocab_size"]))


def param_count(model: dict) -> int:
    """Parameters of the dense block with a tied head: the embedding
    matrix is counted once."""
    h, heads, kv, hd, m, layers, vocab = _dims(model)
    per_layer = (h * heads * hd + 2 * h * kv * hd + heads * hd * h
                 + 3 * h * m + 2 * h)
    return vocab * h + layers * per_layer + h


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward operations a trained token requires: 6 x every
    parameter, the tied matrix once (it is a matmul in the head; the
    embedding lookup is not one), + 12 x layers x hidden x sequence for
    the attention scores and values (the full square, PaLM's convention).
    Recomputed operations (remat) do not count."""
    h, _, _, _, _, layers, _ = _dims(model)
    return 6.0 * param_count(model) + 12.0 * layers * h * seq_len


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    _, _, kv, hd, _, layers, _ = _dims(model)
    return 2 * layers * kv * hd * itemsize


def weight_bytes(model: dict, itemsize: int = 2) -> int:
    return param_count(model) * itemsize


def paged_attention_bytes(context_lens: Iterable[int], model: dict,
                          itemsize: int = 2) -> int:
    """Bytes ONE decode iteration's paged-attention calls must move over
    all layers: the live keys and values of every sequence, + each
    sequence's query row in and output row out.  Page padding and dead
    slots are not needed bytes and are not counted."""
    h, heads, kv, hd, _, layers, _ = _dims(model)
    lens = [int(n) for n in context_lens if n > 0]
    kv_b = sum(lens) * 2 * kv * hd * itemsize
    qo_b = len(lens) * 2 * heads * hd * itemsize
    return layers * (kv_b + qo_b)


def flash_fwd_flops(batch: int, heads: int, seq: int, head_dim: int,
                    causal: bool = True) -> float:
    """Operations one forward flash-attention call needs: QK^T and PV,
    2 x 2 x head_dim for each (query, key) pair that is attended.  Under
    a causal mask that is seq x (seq + 1) / 2 pairs, not the square."""
    pairs = seq * (seq + 1) / 2 if causal else float(seq) * seq
    return 4.0 * batch * heads * pairs * head_dim


def decode_bytes_per_iteration(context_lens: Iterable[int], model: dict,
                               itemsize: int = 2) -> int:
    """Weights read once + the live cache: the memory bound of one
    decode iteration over the whole batch."""
    return weight_bytes(model, itemsize) + paged_attention_bytes(
        context_lens, model, itemsize)
