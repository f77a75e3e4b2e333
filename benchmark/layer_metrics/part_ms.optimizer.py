"""Train step: the `optimizer` scope of `_step_fn`, a step: gradient
constraint and norm, clip, AdamW, `apply_updates`, the bfloat16 casts."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.optimizer", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "optimizer"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
