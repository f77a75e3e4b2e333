"""What the `.swamoe` readers share: the routing counts a run left in its
timeline.json (`moe_lib.step_counts`: the `moe_*` attributes of the recorded
`train.step` span nearest the traced window) turned into rows a layer, and
a kind of attention call's share of its roofline.  A program that records
no counts gives the expectation under even routing; one without the calls
gives None."""

from __future__ import annotations

from typing import List, Optional

from benchmark import arith_swa_moe as arith, moe_lib
from benchmark.layer_lib import peak


def rows_per_layer(cell: dict, counters: dict, trace=None) -> float:
    """Rows the held experts of ONE expert layer were given in a step: the
    run's own count over its expert layers, else the expectation under
    even routing."""
    model = counters["model"]
    counts = moe_lib.step_counts(cell, trace)
    if "moe_rows_held_all_layers" in counts:
        return counts["moe_rows_held_all_layers"] / arith.expert_layers(model)
    return arith.expected_rows_per_token(model) * counters["tokens_per_step"]


def group_sizes(cell: dict, counters: dict, trace=None) -> List[float]:
    """The held experts' rows in one expert layer, spread evenly (only
    their sum and how many are empty enter the kernel's counts)."""
    held = int(counters["model"]["num_experts"])
    return [rows_per_layer(cell, counters, trace) / held] * held


def attention_fwd_roofline(trace, counters: dict, face: str, kind: str
                           ) -> Optional[float]:
    """The forward calls that show `face`: the operations of the layers of
    `kind` over the pairs a query may see, over the bf16 peak, over the
    calls' device time, %.  Every layer of the kind is called equally often
    (twice a step under full remat), so the calls found are shared out
    evenly over them."""
    if trace is None:
        return None
    k = trace.op_time(face)
    layers = len(arith.heads_by_kind(counters["model"])[kind])
    if k["count"] <= 0 or k["seconds"] <= 0 or not layers:
        return None
    tr = counters["train"]
    flops = arith.kind_fwd_flops(tr["batch_rows"] / counters["chips"],
                                 counters["model"], tr["sequence_length"],
                                 kind)
    least_s = k["count"] / layers * flops / peak(counters, "bf16_flops_per_s")
    return 100.0 * least_s / k["seconds"]
