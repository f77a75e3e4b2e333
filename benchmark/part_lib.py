"""The step program's time by the model's parts: what the `part_ms.*`
readers, `bare_copy_ms` and `step_program_hbm_share` share.

A traced run's `timeline.json` carries `programs["train.step"]`, the
program's own report of what it compiled to (ray_tpu/util/device_stats.py
`program_report`): the module's name, for every instruction a trace can
show `name -> [opcode, op_name, holds_matmul, custom_call_target]`, and
the compiled program's memory.  `op_name` holds the named scopes the model
files put their code under (ray_tpu/models/common.py).  Here every
operation the trace shows inside the step module is joined to its row by
instruction name and put in EXACTLY ONE bucket, by the rules below, which
are data.  The buckets' self times and what is left of the module
(`idle_in_program`) sum to the module's time: the identity the tests and
`scripts/opsdump.py --parts` hold them to.

A program that writes no report (an earlier commit, an untraced run) gives
None everywhere, and the readers' metrics are left out of the line.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from benchmark import timeline_lib, trace_reduce

STEP_PROGRAM = "train.step"
KERNEL_TARGET = "tpu_custom_call"

# -- the rules ---------------------------------------------------------------
# 1. a collective, by opcode (`-start` / `-done` forms too), whatever its
#    scope: the quantity `collective_exposed_ms.train4` reads
COLLECTIVE_OPCODES = ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute")
# 2. else by scope: the INNERMOST name of the vocabulary in the row's
#    op_name (the backward and remat's second forward keep it inside
#    `transpose(jvp(..))` / `checkpoint/..`) -> (bucket of a Pallas kernel,
#    bucket of a matmul or a fusion that holds one, bucket of the rest)
_ATTENTION = ("attention_kernels", "attention_proj", "attention_glue")
SCOPE_BUCKETS = {
    "attn.full": _ATTENTION, "attn.sliding": _ATTENTION,
    "attn.cross": _ATTENTION, "attn.gate": _ATTENTION,
    "mla.project": _ATTENTION,
    "mlp": ("mlp",) * 3, "gmu": ("mlp",) * 3,
    "ssm": ("scan",) * 3,
    "moe.experts": ("routed_kernels", "routed_xla", "routed_xla"),
    "moe.route": ("routed_xla",) * 3, "moe.dispatch": ("routed_xla",) * 3,
    "moe.combine": ("routed_xla",) * 3,
    "loss": ("loss",) * 3, "embed": ("loss",) * 3,
    "optimizer": ("optimizer",) * 3,
}
# 3. else
UNSCOPED = "unscoped"
COLLECTIVES = "collectives"
IDLE = "idle_in_program"
BUCKETS = ("attention_kernels", "attention_proj", "attention_glue", "mlp",
           "scan", "routed_kernels", "routed_xla", "loss", "optimizer",
           UNSCOPED, COLLECTIVES)
# Beside the buckets, whatever their scope: operations that only move or
# re-type an array (`bare_copy_ms`).  The TPU compiler names a fusion by
# what it holds.
BARE_COPY_OPCODES = ("copy", "transpose", "convert")
BARE_COPY_FUSION = re.compile(
    r"^(?:copy|transpose|convert|bitcast)(?:_(?:copy|transpose|convert|"
    r"bitcast))*_fusion(?:\.\d+)?$")

_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(
    re.escape(s) for s in sorted(SCOPE_BUCKETS, key=len, reverse=True))
    + r")(?![\w.])")
# an event's name is its HLO line: "%fusion.4 = bf16[8,128] fusion(%a, ..)"
_EVENT = re.compile(r"^%?([\w.\-]+) = ")


def load_report(cell: dict) -> Optional[dict]:
    """`programs["train.step"]` of this run's timeline.json, or None."""
    doc = timeline_lib.load(cell)
    return ((doc or {}).get("programs") or {}).get(STEP_PROGRAM)


def scope_of(op_name: str) -> Optional[str]:
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def instruction_of(event_name: str) -> str:
    """The instruction's name in a trace event's name."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name.lstrip("%")


def opcode_of(event_name: str) -> str:
    """The opcode in a trace event's name ("" where it shows none): what
    an operation WITHOUT a report row is judged by."""
    sig = trace_reduce.TraceView.signature(event_name)
    return sig.split(" ", 1)[0] if " " in sig else ""


def _is_collective(opcode: str) -> bool:
    return any(opcode == c or opcode in (c + "-start", c + "-done")
               for c in COLLECTIVE_OPCODES)


def bucket_of(row: Optional[list], opcode: str) -> str:
    """The one bucket of an operation: `row` its report row (None for an
    operation the report does not know), `opcode` as the trace names it."""
    if _is_collective(row[0] if row else opcode):
        return COLLECTIVES
    if row is None:
        return UNSCOPED
    buckets = SCOPE_BUCKETS.get(scope_of(row[1]))
    if buckets is None:
        return UNSCOPED
    if row[3] == KERNEL_TARGET:
        return buckets[0]
    return buckets[1] if row[2] else buckets[2]


def is_bare_copy(name: str, row: Optional[list], opcode: str) -> bool:
    return ((row[0] if row else opcode) in BARE_COPY_OPCODES
            or bool(BARE_COPY_FUSION.match(name)))


def module_events(trace, plane: str, module: str) -> List[Tuple[float, float]]:
    """(start, end) of the step module's runs on a device: the events of
    the "XLA Modules" line that carry the module's name."""
    rx = re.compile(r"^" + re.escape(module) + r"(?![\w.])")
    return sorted((s, s + d) for n, s, d, _ in
                  trace._line(plane, trace_reduce.MODULES_LINE)
                  if rx.match(n) and d > 0)


def operations(trace, report: dict, plane: str) -> List[dict]:
    """Every operation of the step module on one device, with its self
    time (ns), its report row and its bucket: the events of the "XLA Ops"
    line that start inside one of the module's runs."""
    runs = module_events(trace, plane, report["module"])
    rows = report["instructions"]
    out, i = [], 0
    for name, start, _, self_ns in sorted(
            trace.self_times(plane), key=lambda e: e[1]):
        while i < len(runs) and runs[i][1] <= start:
            i += 1
        if i == len(runs):
            break
        if start < runs[i][0]:
            continue
        inst = instruction_of(name)
        row = rows.get(inst)
        opcode = row[0] if row else opcode_of(name)
        out.append({"name": inst, "event": name, "self": self_ns, "row": row,
                    "scope": scope_of(row[1]) if row else None,
                    "bucket": bucket_of(row, opcode),
                    "bare_copy": is_bare_copy(inst, row, opcode)})
    return out


def tile(trace, report: Optional[dict]) -> Optional[dict]:
    """The step module's time, a step, by bucket (ms, mean over the
    devices), in one pass over its operations: {"step_ms", "steps",
    "parts": {bucket: ms}, `idle_in_program`, "bare_copy_ms", "calls":
    {bucket: operations a step}, "unjoined_ms": the time of operations the
    report has no row for, "by_scope": {(bucket, scope or ""): {"ms",
    "calls"}}, "unscoped_ops": {signature: {"ms", "calls"}} of what no
    scope names}.  `parts` and `idle_in_program` sum to `step_ms`.  None
    without a trace, a report or a run of the module in the trace."""
    if trace is None or not report or not report.get("instructions"):
        return None
    devs = trace.device_planes()
    parts = {b: 0.0 for b in BUCKETS}
    calls = {b: 0.0 for b in BUCKETS}
    by_scope: Dict[Tuple[str, str], dict] = {}
    unscoped_ops: Dict[str, dict] = {}
    module_ns = busy_ns = bare_ns = unjoined_ns = 0.0
    steps = 0

    def count(table, key, self_ns):
        entry = table.setdefault(key, {"ms": 0.0, "calls": 0.0})
        entry["ms"] += self_ns
        entry["calls"] += 1

    for plane in devs:
        runs = module_events(trace, plane, report["module"])
        steps += len(runs)
        module_ns += sum(e - s for s, e in runs)
        for op in operations(trace, report, plane):
            parts[op["bucket"]] += op["self"]
            calls[op["bucket"]] += 1
            busy_ns += op["self"]
            count(by_scope, (op["bucket"], op["scope"] or ""), op["self"])
            if op["bucket"] == UNSCOPED:
                count(unscoped_ops,
                      trace_reduce.TraceView.signature(op["event"]),
                      op["self"])
            if op["bare_copy"]:
                bare_ns += op["self"]
            if op["row"] is None:
                unjoined_ns += op["self"]
    if not steps or module_ns <= 0:
        return None
    per_step = 1e-6 / steps         # ns over all devices -> ms a step
    for table in (by_scope, unscoped_ops):
        for entry in table.values():
            entry["ms"] *= per_step
            entry["calls"] /= steps
    return {"step_ms": module_ns * per_step, "steps": steps / len(devs),
            "parts": {b: v * per_step for b, v in parts.items()},
            IDLE: (module_ns - busy_ns) * per_step,
            "bare_copy_ms": bare_ns * per_step,
            "unjoined_ms": unjoined_ns * per_step,
            "calls": {b: v / steps for b, v in calls.items()},
            "by_scope": by_scope, "unscoped_ops": unscoped_ops}


def part_ms(trace, cell: dict, bucket: str) -> Optional[float]:
    """What a `part_ms.*` reader returns: the bucket's ms a step in this
    run, None where the run left no report."""
    tiled = tiled_run(trace, cell)
    if tiled is None:
        return None
    return tiled[IDLE] if bucket == IDLE else tiled["parts"][bucket]


def tiled_run(trace, cell: dict) -> Optional[dict]:
    """`tile` of this run's trace and report, made once a trace: thirteen
    readers ask."""
    if trace is None:
        return None
    if not hasattr(trace, "_part_tile"):    # as `TraceView._self` is kept
        trace._part_tile = tile(trace, load_report(cell))
    return trace._part_tile
