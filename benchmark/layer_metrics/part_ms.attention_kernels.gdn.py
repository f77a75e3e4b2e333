"""Kernels: the full layer's flash kernels in the step program, a step: the
Pallas calls under `attn.full` (the head-256 forward, remat's second forward
unless out and lse were kept, the one-call backward):
`part_ms.attention_kernels`'s twin for this cell (benchmark/part_lib.py does
the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_kernels.gdn", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-gdn-moe-d4"]
BUCKET = "attention_kernels"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
