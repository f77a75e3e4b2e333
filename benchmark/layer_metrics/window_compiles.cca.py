"""Compilations inside the measured window (JAX's compile events on the
train worker).  Must read 0."""
from benchmark.layer_lib import window_compiles

NAME, UNIT, SOURCE = "window_compiles.cca", "count", "program_counter"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-cca-moe-d4"]


def read(spans, trace, counters, cell):
    return window_compiles(counters)
