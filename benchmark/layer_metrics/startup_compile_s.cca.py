"""Train step: seconds rank 0 spent tracing, lowering, compiling and
loading programs from the persistent cache, over the whole run (JAX's own
compile events, summed by `device_stats`).  One segment of layers, the flash
pair at 8 heads of 128, the routing's sort and gathers at one buffer size and
the grouped kernels at 2048 <-> 2048 compile."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_compile_s.cca", "s", "program_counter"
LAYER, MOVES, WORKLOADS = "train step", "setup_s", ["train-cca-moe-d4"]
PARTS = ("trace_lower_s", "compile_s", "cache_retrieval_s")


def read(spans, trace, counters, cell):
    totals = tl.compile_totals(tl.load(cell))
    return None if totals is None else sum(totals[k] for k in PARTS)
