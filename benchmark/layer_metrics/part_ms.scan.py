"""Kernels: the state-space mixer in the step program, a step: the `ssm` scope
(the selective scan's kernels, its in / out projections and the convolution)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.scan", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-hybrid-d8"]
BUCKET = "scan"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
