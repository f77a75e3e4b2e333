"""Train step: what compressed convolutional attention does between its
projections and the kernels, a step: the self time of the step module's
operations whose `op_name` holds the scope `attn.mix` (the shifted value
half, both convolutions, the q-k mean, the l2 norm and temperature),
forward, remat's second forward and backward alike.  The scope lies INSIDE
`attn.full` and tiles nothing: the same operations are in
`part_ms.attention_glue.cca` and `part_ms.attention_proj.cca`.  Read from the
program's report joined to the trace (benchmark/cca_lib.py)."""
from benchmark import cca_lib

NAME, UNIT, SOURCE = "cca_mix_ms.cca", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-cca-moe-d4"]
SCOPE = "attn.mix"


def read(spans, trace, counters, cell):
    return cca_lib.scope_ms(trace, cell, SCOPE)
