"""Kernels: the mamba mixers in the step program, a step: the `ssm` scope (the
pre-norm, W_in, the convolution, the recurrence's kernels, the gated norm,
W_out): `part_ms.scan`'s twin for this cell (benchmark/part_lib.py does the
work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.scan.ssd", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-ssd-moe-d9"]
BUCKET = "scan"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
