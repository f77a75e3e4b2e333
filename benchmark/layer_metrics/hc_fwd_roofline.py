"""Kernels: the hyper-connection FORWARD kernels' share of their roofline
(`hc_pre_fwd`: the norm's sum, the 2 n + n^2 wide product, the sigmoids, the
Sinkhorn rounds and the lanes' weighted sum from ONE read of the stream;
`hc_post_fwd`: the stream written back through a token's n-vector and n x n
matrix). Memory-bound work: a call's least time is its least bytes (the
stream once in, and once out where it is written, bfloat16; a token's
float32 numbers; the float32 leaf once) over the HBM peak, summed over both
kernels' calls, over their device time in the trace. The configuration's
family (`benchmark/families/`) finds the calls (face `hc_forward`) and counts
their bytes; each call found is counted (under full remat a forward runs
twice a sublayer a step). What the calls' time also holds and the count does
not (the vector unit's float32 arithmetic on a stream that crosses HBM as
bfloat16, the mix's padded row, transposes) keeps the share under 100 %."""
from benchmark.layer_lib import kernel_roofline

NAME, UNIT, SOURCE = "hc_fwd_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "train_tokens_per_s"
FACE = "hc_forward"


def read(spans, trace, counters, cell):
    return kernel_roofline(FACE, trace, counters, cell)
