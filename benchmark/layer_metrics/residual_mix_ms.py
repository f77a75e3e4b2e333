"""Train step: what a residual stream of several lanes costs a step beside
its sublayers: the self time of the step module's operations whose `op_name`
holds the scope `resid.mix` (`models/stack.residual`: ops/hyper_connection.py's
kernels round every sublayer, the collapse behind the stack, and the glue
XLA makes round them: the leaf's bfloat16 parts, the cotangents' sums).
Forward, remat's second forward and backward alike. The scope is OUTSIDE the
sublayers' and none of `part_lib.SCOPE_BUCKETS`, so the same operations lie
in `part_ms.unscoped` until the tiling gives them a bucket. Read from the
program's report joined to the trace (benchmark/cca_lib.py); a program
without the scope gives nothing."""
from benchmark import cca_lib

NAME, UNIT, SOURCE = "residual_mix_ms", "ms", "device_trace"
LAYER, MOVES = "train step", "train_tokens_per_s"
SCOPE = "resid.mix"


def read(spans, trace, counters, cell):
    return cca_lib.scope_ms(trace, cell, SCOPE)
