"""Train step: model FLOP/s utilisation, an END-TO-END utilisation and
named as one: the benchmark's operations a token (`arith.
train_flops_per_token`, remat not counted) x tokens/s over chips x the
bf16 peak.  Not a kernel's roofline share.  Tokens/s here is tokens a step
over the steps' median wall time, because the traced run's own rate has the
profiler's start and stop in it."""
from benchmark.layer_lib import arith, peak

NAME, UNIT, SOURCE = "train_mfu.train", "%", "host_clock"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-d12", "train-fsdp4"]


def read(spans, trace, counters, cell):
    tps = counters.get("steady_tokens_per_s")
    if not tps:
        return None
    flops = arith.train_flops_per_token(
        counters["model"], counters["train"]["sequence_length"])
    return 100.0 * flops * tps / (counters["chips"]
                                  * peak(counters, "bf16_flops_per_s"))
