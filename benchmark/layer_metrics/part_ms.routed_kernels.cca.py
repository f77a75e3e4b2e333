"""Kernels: the grouped-matmul kernels in the step program, a step: the Pallas
calls under `moe.experts`: `part_ms.routed_kernels`'s twin for this cell
(benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.routed_kernels.cca", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "routed_kernels"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
