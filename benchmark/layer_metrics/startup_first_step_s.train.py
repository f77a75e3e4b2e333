"""Train step: host time of rank 0's first `train.init` plus its first
`train.step`: placing the inputs and enqueueing the program, the compile
or the cache load inside them included (the device's own time for the
step is the loop's wait for the loss, which is outside both)."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_first_step_s.train", "s", "program_span"
LAYER, MOVES, WORKLOADS = "train step", "setup_s", ["train-d12", "train-fsdp4"]
SPANS = ("train.init", "train.step")


def read(spans, trace, counters, cell):
    doc = tl.load(cell)
    parts = [tl.first_duration(doc, s) for s in SPANS]
    return None if None in parts else sum(parts)
