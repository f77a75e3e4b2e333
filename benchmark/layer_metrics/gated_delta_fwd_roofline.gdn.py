"""Kernels: the gated-delta-rule FORWARD kernel's share of its roofline.  A
call's least time is the larger of its operations over the bf16 peak
(`arith_gdn.rule_fwd_flops`: the chunked form AT A CHUNK OF 64 whatever
chunk the kernel uses, 2 C^2 (3 d_k + 2 d_v) + 6 C d_k d_v a value head a
chunk) and its least bytes over the HBM peak (`rule_min_bytes`: q and k once
a KEY head, v in, o out, g and beta once), over the forward calls' device
time in the trace.  Under full remat the forward runs twice a linear layer a
step; each call is counted.  The calls' time also holds what is not counted:
the inverse of I + A (ten 64-cube products a chunk), the decays' exps, the
float32 operands going to the MXU as two bfloat16 parts (two or three passes
a product) and, in a call that differentiation follows, writing the blocks'
first states, so the share cannot pass 100 %."""
from benchmark import arith_gdn, gdn_faces, gdn_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "gated_delta_fwd_roofline.gdn", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-gdn-moe-d4"]
KERNEL = gdn_faces.RULE_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    rows, model = gdn_lib.rows_a_chip(counters), counters["model"]
    seq = counters["train"]["sequence_length"]
    least_s = max(
        arith_gdn.rule_fwd_flops(rows, model, seq)
        / peak(counters, "bf16_flops_per_s"),
        arith_gdn.rule_min_bytes(rows, model, seq)
        / peak(counters, "hbm_bytes_per_s"))
    return gdn_lib.calls_roofline(trace, KERNEL, least_s)
