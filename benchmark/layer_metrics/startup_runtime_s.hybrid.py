"""Runtime: from the OS starting the driver process (interpreter and
imports) to `ray_tpu.init` having returned (head, the driver's attach to
its node, the worker template's start)."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_runtime_s.hybrid", "s", "program_span"
LAYER, MOVES, WORKLOADS = "runtime", "setup_s", ["train-hybrid-d8"]
FROM, TO = ("startup.process", "driver"), ("startup.runtime", "driver")


def read(spans, trace, counters, cell):
    return tl.between(tl.load(cell), FROM, TO, "end")
