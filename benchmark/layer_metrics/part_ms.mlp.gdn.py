"""Train step: the gated shared expert and the feed-forward's pre-norm in the
step program, a step: the `mlp` scope: `part_ms.mlp`'s twin for this cell
(benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.mlp.gdn", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-gdn-moe-d4"]
BUCKET = "mlp"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
