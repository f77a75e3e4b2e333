"""Train step: host time of rank 0's first `train.init` plus its first
`train.step` (placing the inputs and enqueueing the program, the compile
or the cache load inside them included)."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_first_step_s.hybrid", "s", "program_span"
LAYER, MOVES, WORKLOADS = "train step", "setup_s", ["train-hybrid-d8"]
SPANS = ("train.init", "train.step")


def read(spans, trace, counters, cell):
    doc = tl.load(cell)
    parts = [tl.first_duration(doc, s) for s in SPANS]
    return None if None in parts else sum(parts)
