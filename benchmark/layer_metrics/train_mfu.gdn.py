"""Train step: model FLOP/s utilisation of the gated-delta-rule / gated-
attention, routed-expert model, an END-TO-END utilisation and named as one:
the benchmark's operations a token (`arith_gdn.train_flops_per_token`: the
matmuls outside the routed experts by kind of layer, the routed experts by
the rows REALLY routed to the experts held here, the triangle's pairs at 4 x
256 a query head, the rule in its chunked form at a chunk of 64 whatever
chunk the kernels use; remat, the inverse, the decays, norms, rope and the
gates not counted) x tokens/s over chips x the bf16 peak.  The rows are the
run's own count (`moe_rows_held_all_layers` of the recorded step nearest the
traced window, from timeline.json) where the run left one, else the
expectation under even routing.  Tokens/s is tokens a step over the steps'
median wall time, because the traced run's own rate has the profiler's start
and stop in it."""
from benchmark import arith_gdn, gdn_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "train_mfu.gdn", "%", "host_clock"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-gdn-moe-d4"]


def read(spans, trace, counters, cell):
    tps = counters.get("steady_tokens_per_s")
    if not tps:
        return None
    rows = gdn_lib.rows_per_layer(cell, counters, trace)
    flops = arith_gdn.train_flops_per_token(
        counters["model"], counters["train"]["sequence_length"],
        rows / counters["tokens_per_step"])
    return 100.0 * flops * tps / (counters["chips"]
                                  * peak(counters, "bf16_flops_per_s"))
