"""Train step: device time of the Mamba-2 / attention / routed-expert model's
step program in the trace, per step."""
from benchmark.layer_lib import program_ms_per_call

NAME, UNIT, SOURCE = "step_ms.ssd", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-ssd-moe-d9"]
PROGRAM = r"_step_fn"


def read(spans, trace, counters, cell):
    return program_ms_per_call(trace, PROGRAM)
