"""What the `.moe` readers share: the routing counts a run left in its
timeline.json.  `ShardedTrainStep` records the `train.step` spans of steps
1, 2, 4, 8, ... whatever the tracing flag says, each with the step's own
metrics as attributes (`moe_rows_held`, `moe_load_max`, `moe_load_mean`,
`moe_rows_bound`: the LAST expert layer's; `moe_rows_held_all_layers`: all
the expert layers' together).  The counts drift as the weights move, so a
reader takes those of the recorded step nearest the traced window's start.
A program that records none (an earlier commit, another model) gives an
empty dict."""

from __future__ import annotations

from typing import Dict, List

from benchmark import arith_moe, timeline_lib as tl


def step_counts(cell: dict, trace=None) -> Dict[str, float]:
    """The `moe_*` attributes of the recorded `train.step` span that began
    nearest `trace.t0_epoch` (the first recorded, with no trace)."""
    doc = tl.load(cell)
    found = [s for s in (tl.spans(doc, "train.step", "rank0") if doc else [])
             if any(k.startswith("moe_") for k in s.get("attributes") or {})]
    if not found:
        return {}
    t0 = getattr(trace, "t0_epoch", None)
    span = found[0] if t0 is None \
        else min(found, key=lambda s: abs(s["start"] - t0))
    return {k: float(v) for k, v in span["attributes"].items()
            if k.startswith("moe_") or k == "step"}


def rows_per_layer(cell: dict, counters: dict, trace=None) -> float:
    """Rows the held experts of ONE expert layer were given in a step: the
    run's own count over its expert layers, else the expectation under
    even routing."""
    model = counters["model"]
    counts = step_counts(cell, trace)
    layers = int(model["num_hidden_layers"]) \
        - int(model["first_k_dense_replace"])
    if "moe_rows_held_all_layers" in counts:
        return counts["moe_rows_held_all_layers"] / layers
    return (arith_moe.expected_rows_per_token(model)
            * counters["tokens_per_step"])


def group_sizes(cell: dict, counters: dict, trace=None) -> List[float]:
    """The held experts' rows in one expert layer, spread evenly (only
    their sum and how many are empty enter the kernel's counts)."""
    held = int(counters["model"]["n_routed_experts"])
    return [rows_per_layer(cell, counters, trace) / held] * held
