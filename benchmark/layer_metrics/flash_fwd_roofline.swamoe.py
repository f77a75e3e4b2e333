"""Kernels: the FULL-causal flash-attention forward calls' share of their
roofline (the full layers: 48 query heads of 128 over the whole triangle,
the half rope inside the kernel).  Compute-bound: the operations over the
causal triangle's (query, key) pairs (`arith_swa_moe.attention_fwd_flops`
with no window: 4 x 128 a visible pair a query head) over the bf16 peak,
over those calls' device time in the trace.  Under full remat the forward
runs twice a full layer a step; each call is counted.  The calls' time also
holds what is not counted: roping the q tile and, once a head, its keys
(all 128 columns pass through the kernel's rope, 64 of them against cos 1
and sin 0), and the scores above the diagonal in the blocks it crosses, so
the share cannot pass 100 %.  A causal call is told by its first operand,
s32[2] (benchmark/swa_moe_faces.py)."""
from benchmark import swa_moe_faces, swa_moe_lib

NAME, UNIT, SOURCE = "flash_fwd_roofline.swamoe", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-swa-moe-d5"]
KERNEL = swa_moe_faces.FORWARD_FULL


def read(spans, trace, counters, cell):
    return swa_moe_lib.attention_fwd_roofline(trace, counters, KERNEL,
                                              "full_attention")
