"""Kernels: the FORWARD grouped-matmul kernel's share of its roofline at
this cell's widths (3072 <-> 1024, 8 held experts).  A call's least time is
the larger of its operations over the bf16 peak and its least bytes over
the HBM peak (`arith_moe.grouped_matmul_flops`, `grouped_matmul_min_bytes`:
the rows present, and each held expert's matrix once), over the forward
calls' device time in the trace.  The three matmuls of an expert layer have
the same two widths, so a call is a call; under full remat the forward runs
twice a layer a step, each call counted.  The rows are the run's own:
`moe_rows_held_all_layers` of the recorded step nearest the traced window
(timeline.json) over the expert layers, spread evenly over the held experts
(only their sum and how many are empty enter the count), else the
expectation under even routing.  Padding rows the kernel multiplies through
(a group's last tile of 256) are not needed work and are not counted, so
the share cannot pass 100 %."""
from benchmark import arith_moe, moe_faces, swa_moe_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "grouped_matmul_roofline.swamoe", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-swa-moe-d5"]
KERNEL = moe_faces.GROUPED_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    k = trace.op_time(KERNEL)
    if k["count"] <= 0 or k["seconds"] <= 0:
        return None
    model = counters["model"]
    sizes = swa_moe_lib.group_sizes(cell, counters, trace)
    wide, narrow = model["hidden_size"], model["moe_intermediate_size"]
    least_s = max(
        arith_moe.grouped_matmul_flops(sizes, wide, narrow)
        / peak(counters, "bf16_flops_per_s"),
        arith_moe.grouped_matmul_min_bytes(sizes, wide, narrow)
        / peak(counters, "hbm_bytes_per_s"))
    return 100.0 * k["count"] * least_s / k["seconds"]
