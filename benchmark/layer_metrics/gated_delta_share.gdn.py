"""Kernels: the gated-delta-rule kernels' (forward and backward) device time
as a share of the step program's, in the trace."""
from benchmark import gdn_faces, gdn_lib

NAME, UNIT, SOURCE = "gated_delta_share.gdn", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-gdn-moe-d4"]
KERNELS = (gdn_faces.RULE_FORWARD, gdn_faces.RULE_BACKWARD)


def read(spans, trace, counters, cell):
    return gdn_lib.kernels_share(trace, KERNELS)
