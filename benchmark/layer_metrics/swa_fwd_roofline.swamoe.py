"""Kernels: the WINDOWED flash-attention forward calls' share of their
roofline (the sliding layers: 72 query heads of 128, window 512, rope over
the whole head inside the kernel).  Compute-bound: the operations over the
(query, key) pairs a query may see under the window
(`arith_swa_moe.attention_fwd_flops`: 4 x 128 a visible pair a query head)
over the bf16 peak, over those calls' device time in the trace.  Under full
remat the forward runs twice a sliding layer a step; each call is counted.
The calls' time also holds what is not counted: roping the q tile and, once
a head, its 8192 keys (the repeated KV heads are roped once for each of
their nine query heads), and the scores of the two blocks a tile visits
that the window's edge and the diagonal mask (half of them), so the share
cannot pass 100 %.  A windowed call is told by its first operand, s32[3]
(benchmark/swa_moe_faces.py)."""
from benchmark import swa_moe_faces, swa_moe_lib

NAME, UNIT, SOURCE = "swa_fwd_roofline.swamoe", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-swa-moe-d5"]
KERNEL = swa_moe_faces.FORWARD_WINDOWED


def read(spans, trace, counters, cell):
    return swa_moe_lib.attention_fwd_roofline(trace, counters, KERNEL,
                                              "sliding_attention")
