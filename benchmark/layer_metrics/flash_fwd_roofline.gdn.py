"""Kernels: the head-256 flash-attention forward calls' share of their
roofline (the full layers: 16 query heads of 256 over 2 KV heads, the whole
triangle, the quarter rope inside the kernel).  Compute-bound: the
operations over the causal triangle's (query, key) pairs
(`arith_gdn.attention_fwd_flops`: 4 x 256 a pair a query head) over the bf16
peak, over those calls' device time in the trace.  Under full remat the
forward runs twice a full layer a step unless the step kept out and lse
(`train.remat`); each call found is counted.  The calls' time also holds
what is not counted: roping the q tile and, once a head, its keys (all 256
columns pass through the kernel's rope, 192 of them against cos 1 and sin
0), and the scores above the diagonal in the blocks it crosses, so the share
cannot pass 100 %."""
from benchmark import arith_gdn, gdn_faces, gdn_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "flash_fwd_roofline.gdn", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-gdn-moe-d4"]
KERNEL = gdn_faces.FLASH_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    flops = arith_gdn.attention_fwd_flops(
        gdn_lib.rows_a_chip(counters), counters["model"],
        counters["train"]["sequence_length"])
    return gdn_lib.calls_roofline(
        trace, KERNEL, flops / peak(counters, "bf16_flops_per_s"))
