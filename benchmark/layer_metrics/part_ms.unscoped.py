"""Train step: operations of the step module under no scope of the vocabulary,
a step: the coverage every other `part_ms.*` rests on."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.unscoped", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "unscoped"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
