"""Train step: the expert sublayer's pre-norm and its residual scaling in the
step program, a step: the `mlp` scope (the model has no dense or shared
feed-forward): `part_ms.mlp`'s twin for this cell (benchmark/part_lib.py
does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.mlp.cca", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "mlp"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
