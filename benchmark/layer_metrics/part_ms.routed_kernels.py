"""Kernels: the grouped-matmul kernels in the step program, a step: the Pallas
custom calls under `moe.experts` (forward, transposed, dw)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.routed_kernels", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "routed_kernels"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
