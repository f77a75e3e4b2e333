"""Kernels: the selective-scan kernels' (forward and backward) device time
as a share of the step program's, in the trace."""
from benchmark import scan_faces

NAME, UNIT, SOURCE = "selective_scan_share.hybrid", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-hybrid-d8"]
PROGRAM = r"_step_fn"
KERNELS = (scan_faces.FORWARD, scan_faces.BACKWARD)


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    step = trace.program_time(PROGRAM)
    found = [trace.op_time(k) for k in KERNELS]
    if step["seconds"] <= 0 or not any(k["count"] for k in found):
        return None
    return 100.0 * sum(k["seconds"] for k in found) / step["seconds"]
