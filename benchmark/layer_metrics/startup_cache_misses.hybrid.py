"""Train step: programs rank 0 compiled that the persistent cache could
have held and did not (each is then written to it): 0 in a warm run, and
the difference between a warm run's `setup_s` and a first run's."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_cache_misses.hybrid", "count", "program_counter"
LAYER, MOVES, WORKLOADS = "train step", "setup_s", ["train-hybrid-d8"]


def read(spans, trace, counters, cell):
    totals = tl.compile_totals(tl.load(cell))
    return None if totals is None else totals["cache_misses"]
