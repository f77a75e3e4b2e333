"""What the `.cca` readers share: the routing counts a run left in its
timeline.json (`moe_lib.step_counts`) turned into rows a layer (EVERY layer
of this model is an expert layer), and the step module's operations found by
what their `op_name` holds: a scope that tiles nothing (`attn.mix`), a scope
that is a part of its own for the first time (`moe.route`: this router is an
MLP with state, not a matrix), a kernel's name where shapes cannot tell two
kernels apart (benchmark/cca_faces.py).  A program that records no counts
gives the expectation under even routing; one without the report, the scope
or the calls gives None."""

from __future__ import annotations

import re
from typing import List, Optional

from benchmark import arith_cca as arith, moe_lib, part_lib
from benchmark.gdn_lib import calls_roofline, rows_a_chip  # noqa: F401


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


def _step_operations(trace, cell: dict) -> Optional[tuple]:
    """(every device's operations of the step module with their report
    rows, the module's runs over all devices), made once a trace: six
    readers ask."""
    if not hasattr(trace, "_cca_operations"):   # as `part_lib.tiled_run`
        report = part_lib.load_report(cell)
        found = None
        if report and report.get("instructions"):
            planes = trace.device_planes()
            found = ([op for plane in planes
                      for op in part_lib.operations(trace, report, plane)],
                     sum(len(part_lib.module_events(trace, plane,
                                                    report["module"]))
                         for plane in planes))
        trace._cca_operations = found
    return trace._cca_operations


def named_operations(trace, cell: dict, pattern: str, kernels_only=False
                     ) -> Optional[dict]:
    """The step module's operations whose `op_name` holds `pattern` (a
    regular expression), a step: {"ms": their self time, "calls": how
    many}, the mean over the devices.  `kernels_only`: Mosaic custom calls
    alone.  None without a trace, a report or one such operation."""
    found = _step_operations(trace, cell) if trace is not None else None
    if not found or not found[1]:
        return None
    operations, steps = found
    rx = re.compile(pattern)
    mine = [op["self"] for op in operations
            if op["row"] is not None and rx.search(op["row"][1] or "")
            and (not kernels_only
                 or op["row"][3] == part_lib.KERNEL_TARGET)]
    if not mine:
        return None
    return {"ms": sum(mine) * 1e-6 / steps, "calls": len(mine) / steps}


def scope_ms(trace, cell: dict, scope: str) -> Optional[float]:
    """Self time a step of the operations under `scope`, forward, remat's
    second forward and backward alike."""
    found = named_operations(
        trace, cell, r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
    return None if found is None else found["ms"]


def named_kernels_roofline(trace, cell: dict, pattern: str,
                           least_s_a_call: float) -> Optional[float]:
    """The kernel calls whose `op_name` holds `pattern`: their count x a
    call's least time, over their device time, %."""
    found = named_operations(trace, cell, pattern, kernels_only=True)
    if found is None or found["ms"] <= 0:
        return None
    return 100.0 * found["calls"] * least_s_a_call / (found["ms"] * 1e-3)


def named_kernels_share(trace, cell: dict, patterns) -> Optional[float]:
    """The device time of the kernel calls whose `op_name` holds any of
    `patterns` as a share of the step module's, %."""
    tiled = part_lib.tiled_run(trace, cell)
    found = [named_operations(trace, cell, p, kernels_only=True)
             for p in patterns]
    if tiled is None or not any(found):
        return None
    return 100.0 * sum(f["ms"] for f in found if f) / tiled["step_ms"]
