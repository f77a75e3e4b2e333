"""Kernels: the flash-attention kernels' self time in the step program, a step:
the Pallas custom calls under the `attn.*` / `mla.*` scopes, forward, remat's
second forward AND backward."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_kernels", "ms", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "attention_kernels"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
