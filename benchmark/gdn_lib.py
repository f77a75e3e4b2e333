"""What the `.gdn` readers share: the routing counts a run left in its
timeline.json (`moe_lib.step_counts`: the `moe_*` attributes of the recorded
`train.step` span nearest the traced window) turned into rows a layer (EVERY
layer of this model is an expert layer), and a kernel's calls shared out
over the layers that make them.  A program that records no counts gives the
expectation under even routing; one without the calls gives None."""

from __future__ import annotations

from typing import List, Optional

from benchmark import arith_gdn as arith, moe_lib


def rows_per_layer(cell: dict, counters: dict, trace=None) -> float:
    """Rows the held experts of ONE layer were given in a step: the run's
    own count over its layers, else the expectation under even routing."""
    model = counters["model"]
    counts = moe_lib.step_counts(cell, trace)
    if "moe_rows_held_all_layers" in counts:
        return counts["moe_rows_held_all_layers"] / int(
            model["num_hidden_layers"])
    return arith.expected_rows_per_token(model) * counters["tokens_per_step"]


def group_sizes(cell: dict, counters: dict, trace=None) -> List[float]:
    """The held experts' rows in one layer, spread evenly (only their sum
    and how many are empty enter the kernel's counts)."""
    held = int(counters["model"]["num_experts"])
    return [rows_per_layer(cell, counters, trace) / held] * held


def rows_a_chip(counters: dict) -> float:
    return counters["train"]["batch_rows"] / counters["chips"]


def calls_roofline(trace, face: str, least_s_a_call: float
                   ) -> Optional[float]:
    """The calls that show `face`: their count x a call's least time, over
    their device time, %."""
    if trace is None:
        return None
    k = trace.op_time(face)
    if k["count"] <= 0 or k["seconds"] <= 0:
        return None
    return 100.0 * k["count"] * least_s_a_call / k["seconds"]


def kernels_share(trace, faces, program: str = r"_step_fn") -> Optional[float]:
    """The device time of the calls that show any of `faces` as a share of
    the step program's, %."""
    if trace is None:
        return None
    step = trace.program_time(program)
    found = [trace.op_time(f) for f in faces]
    if step["seconds"] <= 0 or not any(k["count"] for k in found):
        return None
    return 100.0 * sum(k["seconds"] for k in found) / step["seconds"]

