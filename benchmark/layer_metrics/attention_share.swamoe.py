"""Kernels: all the flash-attention kernels' device time (the windowed and
the full forward calls and the one-call backward of each) as a share of the
step program's, in the trace."""
from benchmark import swa_moe_faces

NAME, UNIT, SOURCE = "attention_share.swamoe", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-swa-moe-d5"]
PROGRAM = r"_step_fn"
KERNELS = (swa_moe_faces.FORWARD_WINDOWED, swa_moe_faces.FORWARD_FULL,
           swa_moe_faces.BACKWARD)


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    step = trace.program_time(PROGRAM)
    found = [trace.op_time(k) for k in KERNELS]
    if step["seconds"] <= 0 or not any(k["count"] for k in found):
        return None
    return 100.0 * sum(k["seconds"] for k in found) / step["seconds"]
