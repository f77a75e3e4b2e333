"""Train step: host time of `ShardedTrainStep.step` (placing the batch
and enqueueing the step program), median over the traced window, from the
program's own annotation on the profiler's clock."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "step_dispatch_ms.hybrid", "ms", "program_span"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-hybrid-d8"]
EVENT = "ray_tpu:train.step"


def read(spans, trace, counters, cell):
    return tl.host_median_ms(trace, EVENT)
