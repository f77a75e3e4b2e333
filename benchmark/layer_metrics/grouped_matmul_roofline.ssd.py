"""Kernels: the FORWARD grouped-matmul kernel's share of its roofline at this
cell's widths (2688 <-> 1856, the narrow one half a lane tile over a whole
number; 8 of 128 experts held, six a token, two matrices an expert).  A
call's least time is the larger of its operations over the bf16 peak and its
least bytes over the HBM peak (`arith_moe.grouped_matmul_flops`,
`grouped_matmul_min_bytes`: the rows present, and each held expert's matrix
once, at the PUBLISHED widths: a padded block's columns are not needed
work), over the forward calls' device time in the trace.  The two matmuls of
a layer have the same two widths, so a call is a call; under full remat the
forward runs twice a layer a step, each call counted.  The calls are found
by the kernel's own name in `op_name` (benchmark/ssd_faces.py, through the
program's report).  The rows are the run's own: `moe_rows_held_all_layers`
of the recorded step nearest the traced window (timeline.json) over the
expert layers, spread evenly over the held experts.  Padding rows the kernel
multiplies through (a group's last tile of 256) are not counted, so the
share cannot pass 100 %; experts that fell to the XLA formulation show no
such call and the reader gives nothing."""
from benchmark import arith_moe, cca_lib, ssd_faces, ssd_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "grouped_matmul_roofline.ssd", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-ssd-moe-d9"]
KERNEL = ssd_faces.GROUPED_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    model = counters["model"]
    sizes = ssd_lib.group_sizes(cell, counters, trace)
    wide, narrow = model["hidden_size"], model["moe_intermediate_size"]
    least_s = max(
        arith_moe.grouped_matmul_flops(sizes, wide, narrow)
        / peak(counters, "bf16_flops_per_s"),
        arith_moe.grouped_matmul_min_bytes(sizes, wide, narrow)
        / peak(counters, "hbm_bytes_per_s"))
    return cca_lib.named_kernels_roofline(trace, cell, KERNEL, least_s)
