"""Trainer: a step's wall time on the host (median over the window)
minus the step program's device time in the trace.  With 4 of 40 layers a
step is short, so the host's share is larger than in the full job."""
from benchmark.layer_lib import percentile, program_ms_per_call

NAME, UNIT, SOURCE = "host_gap_ms.cca", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "trainer", "train_tokens_per_s", ["train-cca-moe-d4"]
PROGRAM = r"_step_fn"


def read(spans, trace, counters, cell):
    ends = counters.get("step_ends") or []
    dev = program_ms_per_call(trace, PROGRAM)
    if len(ends) < 3 or dev is None:
        return None
    walls = [(b - a) * 1e3 / counters["steps_per_sync"]
             for a, b in zip(ends, ends[1:])]
    return percentile(walls, 50) - dev
