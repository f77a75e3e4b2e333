"""Kernels: what the linear mixer does between W_qkvz's product and the
rule's kernels, a step: the self time of the step module's operations whose
`op_name` holds the scope `ssm.chain` (ops/mixer_chain.py's two kernels:
the convolution, SiLU, both l2 norms and the cut into q, k, v, with their
backward; and the sum of dq and dk over a key head's value heads behind the
rule's backward), every forward call and the backward alike.  The
scope lies INSIDE `ssm` and tiles nothing: the same operations are in
`part_ms.scan.gdn`.  A program without the scope (the chain as XLA's own
fusions under `ssm`) gives nothing.  Read from the program's report joined
to the trace (benchmark/cca_lib.py)."""
from benchmark import cca_lib

NAME, UNIT, SOURCE = "mixer_chain_ms.gdn", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "kernels", "train_tokens_per_s", ["train-gdn-moe-d4"]
SCOPE = "ssm.chain"


def read(spans, trace, counters, cell):
    return cca_lib.scope_ms(trace, cell, SCOPE)
