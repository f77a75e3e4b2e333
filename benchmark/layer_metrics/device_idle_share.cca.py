"""Device: 1 - (union of the device-operation intervals) / (traced
window), averaged over the chips used."""
from benchmark.layer_lib import idle_share

NAME, UNIT, SOURCE = "device_idle_share.cca", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "device", "train_tokens_per_s", ["train-cca-moe-d4"]


def read(spans, trace, counters, cell):
    return idle_share(trace)
