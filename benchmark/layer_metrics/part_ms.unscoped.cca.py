"""Train step: operations of the step program no scope of the vocabulary names,
a step: `part_ms.unscoped`'s twin for this cell (benchmark/part_lib.py does
the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.unscoped.cca", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "unscoped"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
