"""Trainer: from `JaxTrainer.fit` entered to the first line of rank 0's
loop: placement group, worker spawn, the backend's start (JAX's import
and the device client among it) and `start_training`."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_worker_s.hybrid", "s", "program_span"
LAYER, MOVES, WORKLOADS = "trainer", "setup_s", ["train-hybrid-d8"]
FROM, TO = ("startup.fit", "driver"), ("startup.loop_entered", "rank0")


def read(spans, trace, counters, cell):
    return tl.between(tl.load(cell), FROM, TO, "start")
