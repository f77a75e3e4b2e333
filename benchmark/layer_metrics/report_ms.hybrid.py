"""Trainer: time inside `train.report`, the wait for the one-item result
queue included (a driver slow to poll blocks the loop there), median over
the traced window, from the program's own annotation."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "report_ms.hybrid", "ms", "program_span"
LAYER, MOVES, WORKLOADS = "trainer", "train_tokens_per_s", ["train-hybrid-d8"]
EVENT = "ray_tpu:train.report"


def read(spans, trace, counters, cell):
    return tl.host_median_ms(trace, EVENT)
