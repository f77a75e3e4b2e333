"""Kernels: the grouped-matmul kernels' (forward, transposed for dx, and
dw) device time as a share of the step program's, in the trace."""
from benchmark import gdn_lib, moe_faces

NAME, UNIT, SOURCE = "grouped_matmul_share.gdn", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-gdn-moe-d4"]
KERNELS = (moe_faces.GROUPED_FORWARD, moe_faces.GROUPED_TRANSPOSED,
           moe_faces.GROUPED_DW)


def read(spans, trace, counters, cell):
    return gdn_lib.kernels_share(trace, KERNELS)
