"""Train step: how far the lanes' mixing matrix is from doubly stochastic:
the worst |row sum - 1| of `comb` over the tokens and both sublayers of the
LAST expert layer (`hc_row_err`, a device scalar of the step's metrics), at
the FIRST recorded step: `ShardedTrainStep` records the `train.step` spans
of steps 1, 2, 4, 8, ... whatever the tracing flag says, each with the
step's own metrics as attributes, and timeline.json carries them
(benchmark/moe_lib.py reads the `moe_*` ones so).  A HEALTH COUNTER: no
faster step should move it, and `MOVES` is only the entry's form.  The
rounds close in slowly on peaked rows: on the cell's seeded weights twenty
leave the worst row of 8192 tokens a few hundredths off 1 (in the float32
reference alike), and one round where twenty are due leaves it near 1.  A
program that records no such attribute (an earlier commit, another model)
gives None."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "hc_comb_row_err", "abs", "program_counter"
LAYER, MOVES = "train step", "train_tokens_per_s"
KEY = "hc_row_err"


def read(spans, trace, counters, cell):
    doc = tl.load(cell)
    for span in (tl.spans(doc, "train.step", "rank0") if doc else []):
        if KEY in (span.get("attributes") or {}):
            return float(span["attributes"][KEY])
    return None
