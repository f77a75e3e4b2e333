"""Train step: everything behind the gradient in the step program, a step:
clip, AdamW over 897 M parameters, the casts (`optimizer`):
`part_ms.optimizer`'s twin for this cell (benchmark/part_lib.py does the
work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.optimizer.cca", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "optimizer"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
