"""Routed experts: the routed path outside its kernels in the step program, a
step: the router's MLP and its carry, top-1, sort, gathers and the SwiGLU
between the grouped matmuls (`moe.route`, `moe.dispatch`, `moe.experts` in
XLA, `moe.combine`): `part_ms.routed_xla`'s twin for this cell
(benchmark/part_lib.py does the work)."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "part_ms.routed_xla.cca", "ms", "device_trace"
LAYER, MOVES = "routed experts", "train_tokens_per_s"
WORKLOADS = ["train-cca-moe-d4"]
BUCKET = "routed_xla"


def read(spans, trace, counters, cell):
    return part_lib.part_ms(trace, cell, BUCKET)
