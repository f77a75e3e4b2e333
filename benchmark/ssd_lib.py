"""What the `.ssd` readers share: the routing counts a run left in its
timeline.json (`moe_lib.step_counts`) turned into rows an EXPERT layer (the
pattern's `E` layers alone hold experts).  A program that records no counts
gives the expectation under even routing."""

from __future__ import annotations

from typing import List

from benchmark import arith_ssd as arith, moe_lib
from benchmark.gdn_lib import rows_a_chip  # noqa: F401


def rows_per_layer(cell: dict, counters: dict, trace=None) -> float:
    """Rows the held experts of ONE expert layer were given in a step: the
    run's own count over its expert layers, else the expectation under even
    routing."""
    model = counters["model"]
    counts = moe_lib.step_counts(cell, trace)
    if "moe_rows_held_all_layers" in counts:
        return counts["moe_rows_held_all_layers"] / arith.layers_of(
            model, arith.EXPERTS)
    return arith.expected_rows_per_token(model) * counters["tokens_per_step"]


def group_sizes(cell: dict, counters: dict, trace=None) -> List[float]:
    """The held experts' rows in one expert layer, spread evenly (only their
    sum and how many are empty enter the kernel's counts)."""
    held = int(counters["model"]["n_routed_experts"])
    return [rows_per_layer(cell, counters, trace) / held] * held
