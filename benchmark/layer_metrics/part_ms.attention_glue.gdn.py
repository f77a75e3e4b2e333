"""Train step: what is left of the full layer in the step program, a step: the
pre-norm, the q / k norms, GQA's repeat, the tables, the element's gate and
the column reordering, under `attn.full` / `attn.gate`:
`part_ms.attention_glue`'s twin for this cell (benchmark/part_lib.py does
the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.attention_glue.gdn", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-gdn-moe-d4"]
BUCKET = "attention_glue"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
