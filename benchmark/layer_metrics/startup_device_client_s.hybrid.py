"""Device: rank 0's first device query, which creates the TPU client."""
from benchmark import timeline_lib as tl

NAME, UNIT, SOURCE = "startup_device_client_s.hybrid", "s", "program_span"
LAYER, MOVES, WORKLOADS = "device", "setup_s", ["train-hybrid-d8"]
SPAN = "startup.device_client"


def read(spans, trace, counters, cell):
    return tl.first_duration(tl.load(cell), SPAN)
