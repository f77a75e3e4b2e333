"""Train step: the two ends of the model, a step: `loss` (final norm, head,
fused cross-entropy) and `embed`."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.loss", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "loss"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
