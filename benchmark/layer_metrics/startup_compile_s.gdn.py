"""Train step: seconds rank 0 spent tracing, lowering, compiling and
loading programs from the persistent cache, over the whole run (JAX's own
compile events, summed by `device_stats`).  Two segments of layers, the
rule's two kernels, the head-256 flash pair, the routing's sort and gathers
at two buffer sizes and the grouped kernels compile."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_compile_s.gdn", "s", "program_counter"
LAYER, MOVES, WORKLOADS = "train step", "setup_s", ["train-gdn-moe-d4"]
PARTS = ("trace_lower_s", "compile_s", "cache_retrieval_s")


def read(spans, trace, counters, cell):
    totals = tl.compile_totals(tl.load(cell))
    return None if totals is None else sum(totals[k] for k in PARTS)
