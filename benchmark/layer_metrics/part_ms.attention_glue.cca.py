"""Train step: what is left of attention in the step program, a step: the
pre-norm, the shifted value half, the depthwise convolution, the q-k mean,
the l2 norm and temperature, the column reordering, GQA's repeat, the tables
and the residual scaling, under `attn.full` (with `attn.mix` inside it):
`part_ms.attention_glue`'s twin for this cell (benchmark/part_lib.py does
the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_glue.cca", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "attention_glue"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
