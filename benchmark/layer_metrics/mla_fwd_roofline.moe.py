"""Kernels: latent attention's flash FORWARD calls' share of their roofline
(keys 192 wide, values 128, nothing padded).  Compute-bound: the operations
over the causal triangle's (query, key) pairs
(`arith_moe.attention_fwd_flops`: 2 x (192 + 128) a visible pair a head, 32
heads) over the bf16 peak, over those calls' device time in the trace.
Under full remat the forward runs twice a layer a step; each call is
counted."""
from benchmark import arith_moe, moe_faces
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "mla_fwd_roofline.moe", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-moe-mla-d6"]
KERNEL = moe_faces.MLA_FORWARD


def read(spans, trace, counters, cell):
    if trace is None:
        return None
    k = trace.op_time(KERNEL)
    if k["count"] <= 0 or k["seconds"] <= 0:
        return None
    tr = counters["train"]
    per_call = arith_moe.attention_fwd_flops(
        tr["batch_rows"] / counters["chips"], counters["model"],
        tr["sequence_length"])
    least_s = k["count"] * per_call / peak(counters, "bf16_flops_per_s")
    return 100.0 * least_s / k["seconds"]
