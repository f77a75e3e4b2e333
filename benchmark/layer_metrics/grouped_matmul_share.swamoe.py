"""Kernels: the grouped-matmul kernels' (forward, transposed for dx, and
dw) device time as a share of the step program's, in the trace."""
from benchmark import moe_faces

NAME, UNIT, SOURCE = "grouped_matmul_share.swamoe", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-swa-moe-d5"]
PROGRAM = r"_step_fn"
KERNELS = (moe_faces.GROUPED_FORWARD, moe_faces.GROUPED_TRANSPOSED,
           moe_faces.GROUPED_DW)


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    step = trace.program_time(PROGRAM)
    found = [trace.op_time(k) for k in KERNELS]
    if step["seconds"] <= 0 or not any(k["count"] for k in found):
        return None
    return 100.0 * sum(k["seconds"] for k in found) / step["seconds"]
