"""Routed experts: the fullest held expert's rows over the held experts'
mean, in the last layer at the recorded step nearest the traced window
(`moe_load_max` / `moe_load_mean`, device scalars of the step's metrics that
the forced `train.step` spans of steps 1, 2, 4, 8, ... carry into
timeline.json).  1 is even routing; all sixteen experts are held, so the
rows are the step's tokens whatever the routing, and an MLP router's
untrained outputs share a token-independent part, so it reads well above 1;
the grouped matmul costs by the rows and by the tiles a group's last one
leaves half empty."""
from benchmark import moe_lib

NAME, UNIT, SOURCE = "expert_load_max_over_mean.cca", "ratio", \
    "program_counter"
LAYER, MOVES, WORKLOADS = "routed experts", "train_tokens_per_s", ["train-cca-moe-d4"]


def read(spans, trace, counters, cell):
    counts = moe_lib.step_counts(cell, trace)
    most, mean = counts.get("moe_load_max"), counts.get("moe_load_mean")
    return None if not most or not mean else most / mean
