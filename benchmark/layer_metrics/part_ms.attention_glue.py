"""Train step: what attention does in XLA between its projections and its
kernels, a step: everything under the `attn.*` / `mla.*` scopes that is neither
a kernel nor holds a matmul (relayouts, GQA's repeat and group sum, rope
tables, the gate's product, norms that fused with none)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_glue", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "attention_glue"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
