"""Train step: model FLOP/s utilisation of the Mamba-2 / attention / routed-
expert model, an END-TO-END utilisation and named as one: the benchmark's
operations a token (`arith_ssd.train_flops_per_token`: matmuls by kind of
layer, the router once, the untied head, the ungated routed experts by the
rows REALLY routed to the experts held here, the triangle's pairs at 4 x 128
a query head, the recurrence in its chunked form AT A CHUNK OF 128 whatever
the kernel uses; remat, the convolution, the decays, the norms and D's term
not counted) x tokens/s over chips x the bf16 peak.  The rows are the run's
own count (`moe_rows_held_all_layers` of the recorded step nearest the
traced window, from timeline.json) where the run left one, else the
expectation under even routing.  Tokens/s is tokens a step over the steps'
median wall time, because the traced run's own rate has the profiler's start
and stop in it."""
from benchmark import arith_ssd, ssd_lib
from benchmark.layer_lib import peak

NAME, UNIT, SOURCE = "train_mfu.ssd", "%", "host_clock"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-ssd-moe-d9"]


def read(spans, trace, counters, cell):
    tps = counters.get("steady_tokens_per_s")
    if not tps:
        return None
    rows = ssd_lib.rows_per_layer(cell, counters, trace)
    flops = arith_ssd.train_flops_per_token(
        counters["model"], counters["train"]["sequence_length"],
        rows / counters["tokens_per_step"])
    return 100.0 * flops * tps / (counters["chips"]
                                  * peak(counters, "bf16_flops_per_s"))
