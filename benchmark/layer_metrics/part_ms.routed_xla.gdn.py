"""Routed experts: the routed path outside its kernels in the step program, a
step: router, top-10, sort, gathers, the conditional's buffers and the
SwiGLU between the grouped matmuls (`moe.route`, `moe.dispatch`,
`moe.experts` in XLA, `moe.combine`): `part_ms.routed_xla`'s twin for this
cell (benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.routed_xla.gdn", "ms", "device_trace"
LAYER, MOVES = "routed experts", "train_tokens_per_s"
WORKLOADS = ["train-gdn-moe-d4"]
BUCKET = "routed_xla"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
