"""Kernels: the state-space-dual recurrence's FORWARD kernel's share of its
roofline.  A call's least time is the larger of its operations over the bf16
peak (`arith_ssd.scan_fwd_flops`: the chunked form AT A CHUNK OF 128
whatever chunk the kernel uses: C B^T once a group, the masked product, the
two products with the state) and its least bytes over the HBM peak
(`scan_min_bytes`: x in and y out once, B and C once a GROUP, dt once), over
the forward calls' device time in the trace; at 164 operations a byte moved,
under the chip's 240, the bytes bind.  Under full remat the forward runs
twice a mamba layer a step; each call is counted.  The calls are found by
the kernel's name in `op_name` (benchmark/ssd_faces.py, through the
program's report).  The calls' time also holds what is not counted: the
decays' exps and masks a head, the float32 operands going to the MXU as two
bfloat16 parts, the running sums read beside dt and, in a call that
differentiation follows, writing the blocks' first states, so the share
cannot pass 100 %."""
from benchmark import arith_ssd, cca_lib, ssd_faces, ssd_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "ssd_fwd_roofline.ssd", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-ssd-moe-d9"]
KERNEL = ssd_faces.SSD_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    rows, model = ssd_lib.rows_a_chip(counters), counters["model"]
    seq = counters["train"]["sequence_length"]
    least_s = max(
        arith_ssd.scan_fwd_flops(rows, model, seq)
        / peak(counters, "bf16_flops_per_s"),
        arith_ssd.scan_min_bytes(rows, model, seq)
        / peak(counters, "hbm_bytes_per_s"))
    return cca_lib.named_kernels_roofline(trace, cell, KERNEL, least_s)
