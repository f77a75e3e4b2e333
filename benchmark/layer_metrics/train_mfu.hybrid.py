"""Train step: model FLOP/s utilisation of the hybrid model, an END-TO-END
utilisation and named as one: the benchmark's operations a token
(`arith_hybrid.train_flops_per_token`: matmuls by kind of layer, the
attention pairs a query may see under window / full / cross, the scan's
elementwise operations; remat not counted) x tokens/s over chips x the
bf16 peak.  Tokens/s is tokens a step over the steps' median wall time,
because the traced run's own rate has the profiler's start and stop in
it."""
from benchmark import arith_hybrid
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "train_mfu.hybrid", "%", "host_clock"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-hybrid-d8"]


def read(spans, trace, counters, cell):
    tps = counters.get("steady_tokens_per_s")
    if not tps:
        return None
    flops = arith_hybrid.train_flops_per_token(
        counters["model"], counters["train"]["sequence_length"])
    return 100.0 * flops * tps / (counters["chips"]
                                  * peak(counters, "bf16_flops_per_s"))
