"""Kernels: the FORWARD grouped-matmul kernel's share of its roofline at
this cell's widths (2048 <-> 512, 32 held experts).  A call's least time is
the larger of its operations over the bf16 peak and its least bytes over
the HBM peak (`arith_moe.grouped_matmul_flops`, `grouped_matmul_min_bytes`:
the rows present, and each held expert's matrix once), over the forward
calls' device time in the trace.  The three matmuls of a layer have the
same two widths, so a call is a call; under full remat the forward runs
twice a layer a step, each call counted.  The rows are the run's own:
`moe_rows_held_all_layers` of the recorded step nearest the traced window
(timeline.json) over the layers, spread evenly over the held experts (only
their sum and how many are empty enter the count), else the expectation
under even routing.  Padding rows the kernel multiplies through (a group's
last tile of 256, against about 320 rows an expert) are not needed work and
are not counted, so the share cannot pass 100 %."""
from benchmark import arith_moe, gdn_lib, moe_faces
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "grouped_matmul_roofline.gdn", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-gdn-moe-d4"]
KERNEL = moe_faces.GROUPED_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    model = counters["model"]
    sizes = gdn_lib.group_sizes(cell, counters, trace)
    wide, narrow = model["hidden_size"], model["moe_intermediate_size"]
    least_s = max(
        arith_moe.grouped_matmul_flops(sizes, wide, narrow)
        / peak(counters, "bf16_flops_per_s"),
        arith_moe.grouped_matmul_min_bytes(sizes, wide, narrow)
        / peak(counters, "hbm_bytes_per_s"))
    return gdn_lib.calls_roofline(trace, KERNEL, least_s)
