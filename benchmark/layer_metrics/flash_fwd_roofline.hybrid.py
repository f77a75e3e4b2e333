"""Kernels: the FULL-causal flash-attention forward calls' share of their
roofline: the full layer's and every cross-attention layer's, the largest
attention cost of a step.  Compute-bound: the operations over the causal
triangle's (query, key) pairs (`arith_hybrid.attention_fwd_flops` with no
window: two softmax maps a query pair over values of twice the head size,
6 x 64 a pair a query head; the zero-padded half of q and k that the call
carries is not counted as work) over the bf16 peak, over those calls'
device time in the trace.  Under full remat the forward runs twice a layer
a step; each call is counted.

A causal call's scalars are [q_off, kv_off], so its first operand is
s32[2] where a windowed call's is s32[3] (`swa_fwd_roofline.hybrid`)."""
from benchmark import arith_hybrid
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "flash_fwd_roofline.hybrid", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-hybrid-d8"]
KERNEL = r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,\d+\]\) custom-call\(s32\[2\] "


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    k = trace.op_time(KERNEL)
    if k["count"] <= 0 or k["seconds"] <= 0:
        return None
    model, tr = counters["model"], counters["train"]
    heads = model["num_attention_heads"]
    per_call = arith_hybrid.attention_fwd_flops(
        tr["batch_rows"] / counters["chips"], heads,
        model["hidden_size"] // heads, tr["sequence_length"], None)
    least_s = k["count"] * per_call / peak(counters, "bf16_flops_per_s")
    return 100.0 * least_s / k["seconds"]
