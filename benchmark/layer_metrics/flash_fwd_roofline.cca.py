"""Kernels: the flash-attention forward calls' share of their roofline (8
query heads of 128 over 2 KV heads, the whole triangle, the half rope inside
the kernel).  Compute-bound: the operations over the causal triangle's
(query, key) pairs (`arith_cca.attention_fwd_flops`: 4 x 128 a pair a query
head) over the bf16 peak, over those calls' device time in the trace.  Under
full remat the forward runs twice a layer a step unless the step kept out
and lse (`train.remat`); each call found is counted.  The calls' time also
holds what is not counted: roping the q tile and, once a head, its keys (all
128 columns pass through the kernel's rope, 64 of them against cos 1 and sin
0), and the scores above the diagonal in the blocks it crosses, so the share
cannot pass 100 %."""
from benchmark import arith_cca, cca_lib, swa_moe_faces
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "flash_fwd_roofline.cca", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-cca-moe-d4"]
KERNEL = swa_moe_faces.FORWARD_FULL


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    flops = arith_cca.attention_fwd_flops(
        cca_lib.rows_a_chip(counters), counters["model"],
        counters["train"]["sequence_length"])
    return cca_lib.calls_roofline(
        trace, KERNEL, flops / peak(counters, "bf16_flops_per_s"))
