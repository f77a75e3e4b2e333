"""Device: the step module's time in which no operation ran, a step: with the
parts it sums to `step_ms.cca`: `part_ms.idle_in_program`'s twin for this
cell (benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.idle_in_program.cca", "ms", "device_trace"
LAYER, MOVES = "device", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "idle_in_program"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
