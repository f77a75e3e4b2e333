"""Device: what a chip must hold to run the step program (the compiled
program's `memory_analysis`: arguments + outputs - aliased + temporaries)
over the device's `bytes_limit`, %: `step_program_hbm_share`'s twin for this
cell, which is sized by this figure."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "step_program_hbm_share.cca", "%", "device_trace"
LAYER, MOVES, WORKLOADS = "device", "train_tokens_per_s", ["train-cca-moe-d4"]


def read(spans, trace, counters, cell):
    report = part_lib.load_report(cell) or {}
    memory, limit = report.get("memory"), report.get("bytes_limit")
    if not memory or not limit:
        return None
    return 100.0 * memory["total_bytes"] / limit
