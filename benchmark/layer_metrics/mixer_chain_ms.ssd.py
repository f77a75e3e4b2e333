"""Kernels: what the mamba mixer does between W_in's product and the
recurrence's kernels and behind them, a step: the self time of the step
module's operations whose `op_name` holds the scope `ssm.chain` (the causal
convolution with its bias, SiLU, the cut into x, B and C, dt's softplus;
the gate and the grouped norm; in XLA by whole tiles), forward, remat's
second forward and backward alike.  The scope lies INSIDE `ssm` and tiles
nothing: the same operations are in `part_ms.scan.ssd`.  Read from the
program's report joined to the trace (benchmark/cca_lib.py)."""
from benchmark import cca_lib

NAME, UNIT, SOURCE = "mixer_chain_ms.ssd", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-ssd-moe-d9"]
SCOPE = "ssm.chain"


def read(spans, trace, counters, cell):
    return cca_lib.scope_ms(trace, cell, SCOPE)
