"""Train step: `copy`, `transpose` and `convert`-only operations of the step
program, a step, whatever their scope (it overlaps the `part_ms.*.cca`
parts): `bare_copy_ms`'s twin for this cell."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "bare_copy_ms.cca", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-cca-moe-d4"]


def read(spans, trace, counters, cell):
    tiled = part_lib.tiled_run(trace, cell)
    return None if tiled is None else tiled["bare_copy_ms"]
