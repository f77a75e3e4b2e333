"""Kernels: the WINDOWED flash-attention forward calls' share of their
roofline.  Compute-bound: the operations over the (query, key) pairs a
query may see under the window (`arith_hybrid.attention_fwd_flops`: two
softmax maps a query pair over values of twice the head size) over the
bf16 peak, over those calls' device time in the trace.  Under full remat
the forward runs twice a window layer a step; each call is counted.

How a windowed call is told from a full one: both take operands of the
same shapes, but a windowed call's scalars are [q_off, kv_off, window], so
its first operand is s32[3] where a causal call's is s32[2]
(ops/attention.py, `_chunk`).  The forward returns (out bf16[heads, seq,
d], log-sum-exp f32[heads, 8, seq]); the backward kernels return one or
two bf16 arrays."""
from benchmark import arith_hybrid
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "swa_fwd_roofline.hybrid", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-hybrid-d8"]
KERNEL = r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,\d+\]\) custom-call\(s32\[3\] "


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
        model["hidden_size"] // heads, tr["sequence_length"],
        model["sliding_window"])
    least_s = k["count"] * per_call / peak(counters, "bf16_flops_per_s")
    return 100.0 * least_s / k["seconds"]
