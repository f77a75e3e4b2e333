"""Collectives: device time of the collective operations during which
nothing else ran on that device, per step, averaged over the chips."""
NAME, UNIT, SOURCE = "collective_exposed_ms.train4", "ms", "device_trace"
LAYER, MOVES, WORKLOADS = "collectives", "train_tokens_per_s", ["train-fsdp4"]
# anchored on the operation's own name: other operations name a
# collective among their operands
COLLECTIVES = r"^%(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"


def read(spans, trace, counters, cell):
    steps = counters.get("trace_steps")
    if trace is None or not steps:
        return None
    return trace.exposed_seconds(COLLECTIVES)["exposed_seconds"] * 1e3 / steps
