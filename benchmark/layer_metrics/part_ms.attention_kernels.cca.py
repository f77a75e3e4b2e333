"""Kernels: the flash kernels in the step program, a step: the Pallas calls
under `attn.full` (the forward at 8 heads of 128 with the half rope inside,
remat's second forward unless out and lse were kept, the one-call backward):
`part_ms.attention_kernels`'s twin for this cell (benchmark/part_lib.py does
the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_kernels.cca", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "attention_kernels"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
