"""Kernels: the four hyper-connection kernels' (`hc_pre_fwd`, `hc_post_fwd`
and their backward passes) device time as a share of the step program's, in
the trace; the calls found as the configuration's family finds them (face
`hc_all`)."""
from benchmark.layer_lib import kernels_share

NAME, UNIT, SOURCE = "hc_share", "%", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
FACE = "hc_all"


def read(spans, trace, counters, cell):
    return kernels_share(FACE, trace, cell)
