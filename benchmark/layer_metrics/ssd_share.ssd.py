"""Kernels: the state-space-dual recurrence's kernels' (forward and
backward) device time as a share of the step program's, in the trace; the
calls found by the kernels' names in `op_name` (benchmark/ssd_faces.py)."""
from benchmark import cca_lib, ssd_faces

NAME, UNIT, SOURCE = "ssd_share.ssd", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-ssd-moe-d9"]
KERNELS = (ssd_faces.SSD_FORWARD, ssd_faces.SSD_BACKWARD)


def read(spans, trace, counters, cell):
    return cca_lib.named_kernels_share(trace, cell, KERNELS)
