"""Kernels: the linear mixer in the step program, a step: the `ssm` scope, here
a recurrent mixer (the gated delta rule's kernels, W_qkvz, W_ba, the
convolution, the l2 norms, the gated norm, W_o): `part_ms.scan`'s twin for
this cell (benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.scan.gdn", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-gdn-moe-d4"]
BUCKET = "scan"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
