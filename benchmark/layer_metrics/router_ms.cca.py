"""Routed experts: the router, a step: the self time of the step module's
operations whose `op_name` holds the scope `moe.route` (the float32
down-projection, the carry from the layer before, the norm, the three-matrix
GELU MLP, the softmax and the selection), forward, remat's second forward
and backward alike: the first router here that is a part and not a matrix.
The same operations are in `part_ms.routed_xla.cca`.  Read from the
program's report joined to the trace (benchmark/cca_lib.py)."""
from benchmark import cca_lib

NAME, UNIT, SOURCE = "router_ms.cca", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "routed experts", "train_tokens_per_s", ["train-cca-moe-d4"]
SCOPE = "moe.route"


def read(spans, trace, counters, cell):
    return cca_lib.scope_ms(trace, cell, SCOPE)
