"""Kernels: the grouped-matmul kernels' (forward, transposed for dx, and
dw) device time as a share of the step program's, in the trace; the calls
found by the kernels' names in `op_name` (benchmark/cca_faces.py)."""
from benchmark import cca_faces, cca_lib

NAME, UNIT, SOURCE = "grouped_matmul_share.cca", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-cca-moe-d4"]
KERNELS = (cca_faces.GROUPED_FORWARD, cca_faces.GROUPED_TRANSPOSED,
           cca_faces.GROUPED_DW)


def read(spans, trace, counters, cell):
    return cca_lib.named_kernels_share(trace, cell, KERNELS)
