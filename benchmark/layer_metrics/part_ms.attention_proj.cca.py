"""Train step: attention's matmuls in the step program, a step: W_q, W_k, W_v1,
W_v2, the head-mixing convolution's d x d taps, W_o and their gradients,
under `attn.full`: `part_ms.attention_proj`'s twin for this cell
(benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_proj.cca", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "attention_proj"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
