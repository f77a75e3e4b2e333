"""Train step: the feed-forward matmuls and their elementwise work, a step:
the `mlp` scope (dense SwiGLU, the shared experts) and `gmu`."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.mlp", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "mlp"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
