"""Train step: attention's projections in the step program, a step: the
matmul-holding fusions under the `attn.*` / `mla.*` scopes (W_q, W_k, W_v, W_g,
W_o, the latent projections, and their weight gradients)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_proj", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "attention_proj"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
