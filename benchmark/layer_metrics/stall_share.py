"""Trainer: the share of the window's wall that its stalled steps spent over
a median step, from the program's own step ledger (`steps` in the run's
timeline.json: one row a step, `[step, t_enter, dispatch_s, report_s,
flags]`; rank 0's). The window's steps are the last rows; an interval runs
from a row's `t_enter` to the next row's; one that touches a step entered
under a running profile is left out (the profiler's start and stop cost
seconds and are the traced run's alone). 0.0 in a quiet window."""
import statistics

from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "stall_share", "%", "program_counter"
LAYER, MOVES = "trainer", "train_tokens_per_s"
PROFILED = 1            # the flags' bit: a profile ran when the step entered
STALLED = 1.25          # medians: far over routing's 4 %, far under a lost step
FEWEST_INTERVALS = 8


def read(spans, trace, counters, cell):
    ends = counters.get("step_ends")
    doc = tl.load(cell) if ends else None
    rows = ((doc or {}).get("steps") or {}).get("rank0", {}).get("rows")
    if not rows:
        return None
    rows = rows[-len(ends) * counters["steps_per_sync"]:]
    kept = [b[1] - a[1] for a, b in zip(rows, rows[1:])
            if not (a[4] | b[4]) & PROFILED]
    if len(kept) < FEWEST_INTERVALS:
        return None
    median = statistics.median(kept)
    return 100.0 * sum(w - median for w in kept
                       if w > STALLED * median) / sum(kept)
