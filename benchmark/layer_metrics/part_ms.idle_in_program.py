"""Device: the step module's time less the union of its operations'
intervals, a step: idle between two operations of ONE module, which
`host_gap_ms.*` (wall time less the module's) cannot see."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.idle_in_program", "ms", "device_trace"
LAYER, MOVES = "device", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "idle_in_program"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
