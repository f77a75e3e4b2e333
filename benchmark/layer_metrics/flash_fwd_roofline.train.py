"""Kernels: the flash-attention forward kernel's share of its roofline.
Compute-bound: the operations its calls need (`arith.flash_fwd_flops`,
causal pairs only, from the shapes one chip sees) over the bf16 peak,
over the kernel's device time in the trace.  Under full remat the
forward kernel runs twice a layer a step; each call is counted."""
from benchmark.layer_lib import arith, peak

NAME, UNIT, SOURCE = "flash_fwd_roofline.train", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-d12", "train-fsdp4"]
# A Pallas kernel has no name of its own in the trace: it is a custom-call
# whose HLO line gives result and operands.  The forward kernel returns
# (out bf16[b*heads, seq, head_dim], log-sum-exp f32[b*heads, 8, seq]) and
# takes the s32[2] block counts first (ops/attention.py); the backward
# kernels return one or two bf16 arrays.
KERNEL = r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,\d+\]\) custom-call\(s32\[2\] "


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    k = trace.op_time(KERNEL)
    if k["count"] <= 0 or k["seconds"] <= 0:
        return None
    model, tr = counters["model"], counters["train"]
    rows = tr["batch_rows"] / counters["chips"]
    head_dim = model.get("head_dim") or (model["hidden_size"]
                                         // model["num_attention_heads"])
    per_call = arith.flash_fwd_flops(rows, model["num_attention_heads"],
                                     tr["sequence_length"], head_dim)
    least_s = k["count"] * per_call / peak(counters, "bf16_flops_per_s")
    return 100.0 * least_s / k["seconds"]
