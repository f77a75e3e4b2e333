"""Routed experts: the routed path's XLA half, a step: `moe.route`,
`moe.dispatch` (with the `lax.cond` between the two buffer sizes),
`moe.combine` and what of `moe.experts` is not a kernel."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.routed_xla", "ms", "device_trace"
LAYER, MOVES = "routed experts", "train_tokens_per_s"
WORKLOADS = ["train-moe-mla-d6", "train-swa-moe-d5"]
BUCKET = "routed_xla"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
