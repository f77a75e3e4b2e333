"""Device: what a chip must hold to run the step program (the compiled
program's `memory_analysis`: arguments + outputs - aliased + temporaries)
over the device's `bytes_limit`, %.  The cells are sized by this figure;
`memory_peak_bytes` under-reads it by a third or more."""
from benchmark import part_lib

NAME, UNIT, SOURCE = "step_program_hbm_share", "%", "device_trace"
LAYER, MOVES = "device", "train_tokens_per_s"
WORKLOADS = ["train-d12", "train-fsdp4", "train-hybrid-d8", "train-moe-mla-d6", "train-swa-moe-d5"]


def read(spans, trace, counters, cell):
    report = part_lib.load_report(cell) or {}
    memory, limit = report.get("memory"), report.get("bytes_limit")
    if not memory or not limit:
        return None
    return 100.0 * memory["total_bytes"] / limit
