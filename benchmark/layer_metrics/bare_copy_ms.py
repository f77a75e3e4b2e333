"""Train step: `copy`, `transpose` and `convert`-only operations of the step
program, a step, whatever their scope (it overlaps the `part_ms.*` parts):
arrays moved or re-typed and nothing else, the line PR 38 emptied."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "bare_copy_ms", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]


def read(spans, trace, counters, cell):
    tiled = part_lib.tiled_run(trace, cell)
    return None if tiled is None else tiled["bare_copy_ms"]
