"""Compilations inside the measured window (the program's own count for
serving, JAX's compile events for training).  Must read 0."""
from benchmark.layer_lib import window_compiles

NAME, UNIT, SOURCE = "window_compiles.train", "count", "program_counter"
LAYER, MOVES, WORKLOADS = "train step", "train_tokens_per_s", ["train-d12", "train-fsdp4"]


def read(spans, trace, counters, cell):
    return window_compiles(counters)
