"""Train step: host time of rank 0's first `train.init` plus its first
`train.step` (placing the inputs and enqueueing the program, the compile
or the cache load inside them included; this model's first step also
waits for its routing counts, so the step's device time is in it)."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_first_step_s.cca", "s", "program_span"
LAYER, MOVES, WORKLOADS = "train step", "setup_s", ["train-cca-moe-d4"]
SPANS = ("train.init", "train.step")


def read(spans, trace, counters, cell):
    doc = tl.load(cell)
    parts = [tl.first_duration(doc, s) for s in SPANS]
    return None if None in parts else sum(parts)
