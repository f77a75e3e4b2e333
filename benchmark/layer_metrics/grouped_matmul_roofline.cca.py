"""Kernels: the FORWARD grouped-matmul kernel's share of its roofline at
this cell's widths (2048 <-> 2048, all 16 experts held, one a token).  A
call's least time is the larger of its operations over the bf16 peak and its
least bytes over the HBM peak (`arith_moe.grouped_matmul_flops`,
`grouped_matmul_min_bytes`: the rows present, and each held expert's matrix
once), over the forward calls' device time in the trace.  The three matmuls
of a layer have the same two widths, so a call is a call; under full remat
the forward runs twice a layer a step, each call counted.  The experts are
SQUARE, so shapes cannot tell the forward call from the transposed one that
gives dx: the calls are found by the kernel's own name in `op_name`
(benchmark/cca_faces.py, through the program's report).  The rows are the
run's own: `moe_rows_held_all_layers` of the recorded step nearest the
traced window (timeline.json) over the layers (the step's tokens, with all
sixteen held), spread evenly over the held experts (only their sum and how
many are empty enter the count).  Padding rows the kernel multiplies through
(a group's last tile of 256) are not needed work and are not counted, so the
share cannot pass 100 %."""
from benchmark import arith_moe, cca_faces, cca_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "grouped_matmul_roofline.cca", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-cca-moe-d4"]
KERNEL = cca_faces.GROUPED_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    model = counters["model"]
    sizes = cca_lib.group_sizes(cell, counters, trace)
    wide, narrow = model["hidden_size"], model["moe_intermediate_size"]
    least_s = max(
        arith_moe.grouped_matmul_flops(sizes, wide, narrow)
        / peak(counters, "bf16_flops_per_s"),
        arith_moe.grouped_matmul_min_bytes(sizes, wide, narrow)
        / peak(counters, "hbm_bytes_per_s"))
    return cca_lib.named_kernels_roofline(trace, cell, KERNEL, least_s)
