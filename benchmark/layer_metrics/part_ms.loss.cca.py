"""Train step: embedding, final norm, the TIED head and fused cross-entropy in
the step program, a step: the `loss` and `embed` scopes: `part_ms.loss`'s
twin for this cell (benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.loss.cca", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "loss"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
