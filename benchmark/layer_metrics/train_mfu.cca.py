"""Train step: model FLOP/s utilisation of the compressed-convolutional-
attention, top-1-expert model, an END-TO-END utilisation and named as one:
the benchmark's operations a token (`arith_cca.train_flops_per_token`: the
projections, the head-mixing convolution's taps, the router's four matrices
once whatever passes float32 costs them, the tied head, the routed experts by
the rows REALLY routed to the experts held here, the triangle's pairs at 4 x
128 a query head; remat, the depthwise convolution, the mean, the norms, rope
and the residual scaling not counted) x tokens/s over chips x the bf16 peak.
The rows are the run's own count (`moe_rows_held_all_layers` of the recorded
step nearest the traced window, from timeline.json) where the run left one,
else the expectation under even routing.  Tokens/s is tokens a step over the
steps' median wall time, because the traced run's own rate has the
profiler's start and stop in it."""
from benchmark import arith_cca, cca_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "train_mfu.cca", "%", "host_clock"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-cca-moe-d4"]


def read(spans, trace, counters, cell):
    tps = counters.get("steady_tokens_per_s")
    if not tps:
        return None
    rows = cca_lib.rows_per_layer(cell, counters, trace)
    flops = arith_cca.train_flops_per_token(
        counters["model"], counters["train"]["sequence_length"],
        rows / counters["tokens_per_step"])
    return 100.0 * flops * tps / (counters["chips"]
                                  * peak(counters, "bf16_flops_per_s"))
