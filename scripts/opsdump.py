#!/usr/bin/env python
"""opsdump — export a past window of the durable ops journal as a
Perfetto-loadable chrome trace.

The live dashboard (`/api/trace`) can only show what the current head
holds in memory; this reads the on-disk journal segments directly
(no cluster required — works on a dead cluster's journal dir), merges
the "spans", "flight", "metrics" and "device" streams, and writes one
chrome trace JSON:

    python scripts/opsdump.py --dir /var/ray_tpu/ops \\
        --last 3600 --out trace.json
    python scripts/opsdump.py --dir $RAY_TPU_OPS_JOURNAL_DIR --stats

Lanes follow the dashboard convention: harvested spans render on each
worker's OS-pid lane, flight-recorder events are instant markers on a
per-category lane, and scalar metrics become counter tracks.  Serve
request-journey spans (`serve.*`, tagged with a trace id) get their
own process with one named lane per request, so each journey's phases
read as nested slices on a single row.  Device-plane records become
roofline/MFU counter tracks plus instant recompile markers on a
"device plane" process.  `--since` / `--until` take epoch seconds;
`--last N` means "the last N seconds".

A profiler trace joins the same picture: `--xplane <file>.xplane.pb`
lays the device's program executions ("XLA Modules") and the host
annotations (`ray_tpu:<span>` and any other `<prefix>:<name>`) beside
the spans, moved from the profiler's clock to the epoch clock by the
`t_epoch` every `ray_tpu:` annotation carries (ray_tpu/util/tracing.py);
`--timeline <run_dir>/timeline.json` adds a train run's start-up and
train-step spans (JaxTrainer.fit writes the file).  Neither needs a
journal:

    python scripts/opsdump.py --xplane t.xplane.pb \
        --timeline run/timeline.json --out trace.json
    python scripts/opsdump.py --xplane t.xplane.pb --stats  # the offset

`--parts` prints, from the same two files of a TRACED train run, the step
program's time by the model's parts: every operation the device ran
inside the step module, joined by instruction name to the
`programs["train.step"]` report in timeline.json (its `op_name` holds the
model files' named scopes) and summed by part, with what is left of the
module as `idle_in_program` and the compiled program's memory
(benchmark/part_lib.py holds the rules; PERF.md section 5 is made so):

    python scripts/opsdump.py --xplane t.xplane.pb \
        --timeline run/timeline.json --parts

`--steps` prints the run's step ledger from timeline.json alone (every
run has one, traced or not: `ray_tpu/train/session.py`): the steps' wall
by the program's own clock (median, p99, longest; the first quarter's
median against the last's: a drift), and every step over 1.25 x the
median with where its time went (dispatch, report, compile, the loop's
own) and its flags.  With `--xplane` beside it, each traced step's idle
time on the device by the program's annotation that covers it:

    python scripts/opsdump.py --timeline run/timeline.json --steps
    python scripts/opsdump.py --timeline run/timeline.json --steps \
        --xplane t.xplane.pb
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ray_tpu.train import session  # noqa: E402
from ray_tpu.util import journal  # noqa: E402
from ray_tpu.util.tracing import (  # noqa: E402
    ANNOTATION_PREFIX,
    span_row_to_dict,
    spans_to_chrome_events,
)

STREAMS = ("spans", "flight", "metrics", "device")
# One synthetic chrome pid per flight-recorder category lane.
_FLIGHT_PID = 0
# Synthetic process holding the per-request serve lanes: one named
# thread per trace id, so each request's journey (queue → prefill →
# handoff_pull → decode → stream) reads as nested slices on its own
# row even when the phases ran in different OS processes.
_SERVE_PID = 1 << 22
# Synthetic process for device-plane telemetry (roofline/MFU counter
# tracks + recompile instant markers), one thread lane per OS pid.
_DEVICE_PID = (1 << 22) + 1
# Synthetic processes for a profiler trace: one per xplane plane.
_XPLANE_PID = (1 << 22) + 16
# A host annotation somebody named on purpose: "<prefix>:<name>" (ours,
# a benchmark's "bench:"), not the runtime's own "Class::Method" events.
_ANNOTATION = re.compile(r"^\w+:[^:\s]")
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def serve_request_events(spans: List[dict]) -> List[Dict[str, Any]]:
    """serve.* spans grouped by trace id → one named lane per request."""
    by_req: Dict[str, List[dict]] = {}
    for s in spans:
        by_req.setdefault(s.get("trace_id", ""), []).append(s)
    events: List[Dict[str, Any]] = []
    lanes = sorted(by_req.items(),
                   key=lambda kv: min(x["start"] for x in kv[1]))
    for tid, (trace_id, group) in enumerate(lanes):
        for s in group:
            events.append({
                "cat": "serve", "name": s["name"], "ph": "X",
                "pid": _SERVE_PID, "tid": tid,
                "ts": s["start"] * 1e6,
                "dur": max(0.0, s["end"] - s["start"]) * 1e6,
                "args": {**s["attributes"], "span_id": s["span_id"],
                         "parent_id": s["parent_id"],
                         "trace_id": trace_id},
            })
        events.append({"ph": "M", "pid": _SERVE_PID, "tid": tid,
                       "name": "thread_name",
                       "args": {"name": f"req {trace_id[:8] or '?'}"}})
    if events:
        events.append({"ph": "M", "pid": _SERVE_PID,
                       "name": "process_name",
                       "args": {"name": "serve requests"}})
    return events


def span_events(envs: List[dict]) -> List[Dict[str, Any]]:
    """Journal span rows → X slices, one lane per (pid, worker);
    serve-plane request spans additionally fan out by trace id."""
    by_lane: Dict[tuple, List[dict]] = {}
    serve_spans: List[dict] = []
    for env in envs:
        row = env.get("d")
        if not isinstance(row, list) or len(row) < 7:
            continue
        s = span_row_to_dict(row)
        if s["name"].startswith("serve.") and s.get("trace_id"):
            serve_spans.append(s)
            continue
        key = (int(s.get("pid") or 0), s.get("worker", ""))
        by_lane.setdefault(key, []).append(s)
    events: List[Dict[str, Any]] = []
    for (pid, whex), spans in sorted(by_lane.items()):
        events.extend(spans_to_chrome_events(
            spans, pid=pid or 1,
            process_name=f"worker spans {whex[:8]}" if whex
            else "driver spans",
            sort_index=pid or 1))
    events.extend(serve_request_events(serve_spans))
    return events


def flight_events(envs: List[dict]) -> List[Dict[str, Any]]:
    """Flight-recorder events → instant markers, one thread lane per
    category (wire/scheduler/object/health)."""
    events: List[Dict[str, Any]] = []
    lanes: Dict[str, int] = {}
    for env in envs:
        ev = env.get("d")
        if not isinstance(ev, dict) or "ts" not in ev:
            continue
        cat = str(ev.get("category", "?"))
        tid = lanes.setdefault(cat, len(lanes))
        args = {k: v for k, v in ev.items()
                if k not in ("ts", "category", "event")}
        events.append({
            "cat": "flight", "name": str(ev.get("event", "?")),
            "ph": "i", "s": "t", "pid": _FLIGHT_PID, "tid": tid,
            "ts": float(ev["ts"]) * 1e6, "args": args})
    if events:
        events.append({"ph": "M", "pid": _FLIGHT_PID,
                       "name": "process_name",
                       "args": {"name": "flight recorder"}})
        for cat, tid in lanes.items():
            events.append({"ph": "M", "pid": _FLIGHT_PID, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": cat}})
    return events


def metric_events(envs: List[dict]) -> List[Dict[str, Any]]:
    """Metrics snapshots → counter tracks (scalar series summed over
    tags; histogram series plot their sample count)."""
    events: List[Dict[str, Any]] = []
    for env in envs:
        rec = env.get("d")
        if not isinstance(rec, dict):
            continue
        ts = float(env.get("t", 0.0)) * 1e6
        pid = int(env.get("p", 0))
        for snap in rec.get("snapshots", []):
            total = 0.0
            for _, val in snap.get("series", []):
                if isinstance(val, (int, float)):
                    total += float(val)
                elif isinstance(val, list) and len(val) == 3:
                    total += float(val[2])  # histogram count
            events.append({
                "cat": "metrics", "name": snap.get("name", "?"),
                "ph": "C", "pid": pid, "tid": 0, "ts": ts,
                "args": {"value": total}})
    return events


def device_events(envs: List[dict]) -> List[Dict[str, Any]]:
    """Device journal records → counter tracks for the continuous
    roofline/MFU step windows and instant markers for compile events
    (a recompile storm reads as a burst of markers over a sagging
    roofline track)."""
    events: List[Dict[str, Any]] = []
    lanes: Dict[int, int] = {}
    for env in envs:
        rec = env.get("d")
        if not isinstance(rec, dict):
            continue
        pid = int(env.get("p", 0))
        tid = lanes.setdefault(pid, len(lanes))
        ts = float(rec.get("ts") or env.get("t", 0.0)) * 1e6
        kind = rec.get("kind")
        if kind == "step":
            plane = rec.get("plane", "?")
            for field in ("roofline_fraction", "mfu"):
                val = rec.get(field)
                if isinstance(val, (int, float)):
                    events.append({
                        "cat": "device",
                        "name": f"{field}[{plane}]",
                        "ph": "C", "pid": _DEVICE_PID, "tid": tid,
                        "ts": ts, "args": {"value": float(val)}})
            tok_s = rec.get("tokens_per_s")
            if isinstance(tok_s, (int, float)):
                events.append({
                    "cat": "device", "name": f"tokens_per_s[{plane}]",
                    "ph": "C", "pid": _DEVICE_PID, "tid": tid,
                    "ts": ts, "args": {"value": float(tok_s)}})
        elif kind == "compile":
            args = {k: rec.get(k) for k in (
                "wall_s", "shapes", "count", "after_warmup")}
            events.append({
                "cat": "device",
                "name": f"compile {rec.get('function', '?')}",
                "ph": "i", "s": "t", "pid": _DEVICE_PID, "tid": tid,
                "ts": ts, "args": args})
    if events:
        events.append({"ph": "M", "pid": _DEVICE_PID,
                       "name": "process_name",
                       "args": {"name": "device plane"}})
        for pid, tid in lanes.items():
            events.append({"ph": "M", "pid": _DEVICE_PID, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": f"pid {pid}"}})
    return events


def read_xplane(path: str) -> Dict[str, Dict[str, list]]:
    """{plane: {line: [(name, start_ns, duration_ns, stats)]}} of the
    device planes' "XLA Modules" lines and the host planes' annotations."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, list]] = {}
    for plane in ProfileData.from_file(path).planes:
        device = bool(_DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != "XLA Modules":
                continue
            kept = [(e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats) if not device else {})
                    for e in line.events
                    if device or _ANNOTATION.match(e.name)]
            if kept:
                planes.setdefault(plane.name, {})[line.name] = kept
    return planes


def clock_offset(planes: Dict[str, Dict[str, list]]
                 ) -> Optional[Tuple[float, float, int]]:
    """(offset_s, spread_s, n): epoch = profiler time + offset, the
    median over every `ray_tpu:` annotation that carries `t_epoch`, with
    the widest disagreement between two of them; None when none does."""
    offsets = [float(st["t_epoch"]) - start / 1e9
               for lines in planes.values() for evs in lines.values()
               for name, start, _, st in evs
               if name.startswith(ANNOTATION_PREFIX) and "t_epoch" in st]
    if not offsets:
        return None
    return (statistics.median(offsets), max(offsets) - min(offsets),
            len(offsets))


def xplane_events(planes: Dict[str, Dict[str, list]], offset_s: float
                  ) -> List[Dict[str, Any]]:
    """The trace's events as chrome slices on the epoch clock."""
    events: List[Dict[str, Any]] = []
    for i, (plane, lines) in enumerate(sorted(planes.items())):
        pid = _XPLANE_PID + i
        for tid, (line, evs) in enumerate(sorted(lines.items())):
            for name, start, dur, st in evs:
                events.append({
                    "cat": "xplane", "name": name[:120], "ph": "X",
                    "pid": pid, "tid": tid,
                    "ts": start / 1e3 + offset_s * 1e6, "dur": dur / 1e3,
                    "args": {k: v for k, v in st.items()
                             if isinstance(v, (str, int, float))}})
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": line}})
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": f"profile {plane}"}})
    return events


def timeline_events(path: str) -> List[Dict[str, Any]]:
    """A train run's timeline.json: each process's spans on its pid."""
    with open(path) as f:
        doc = json.load(f)
    by_proc: Dict[tuple, List[dict]] = {}
    for s in doc.get("spans", []) + doc.get("compiles", []):
        by_proc.setdefault((s.get("pid", 0), s.get("worker", "")),
                           []).append(s)
    events: List[Dict[str, Any]] = []
    for (pid, worker), spans in sorted(by_proc.items()):
        events.extend(spans_to_chrome_events(
            spans, pid=pid or 1, process_name=f"train {worker} ({pid})"))
    return events


def parts_table(xplane: str, timeline: str, top: int = 8) -> str:
    """The step program's time by part, as text: ms a step, share of the
    step module, operations a step, by bucket and under it by scope; the
    heaviest operations under no scope; the compiled program's memory.
    `xplane`: an .xplane.pb, or a trace saved by `TraceView.to_json`."""
    from benchmark import part_lib, trace_reduce

    with open(timeline) as f:
        report = (json.load(f).get("programs") or {}).get(
            part_lib.STEP_PROGRAM)
    if not report:
        raise SystemExit(f"{timeline} holds no programs[\"train.step\"]: "
                         "only a traced run writes the program's report")
    trace = (trace_reduce.from_json(xplane)
             if xplane.endswith((".json", ".json.gz"))
             else trace_reduce.load_xplane(xplane))
    tiled = part_lib.tile(trace, report)
    if tiled is None:
        raise SystemExit(f"{xplane} shows no run of {report['module']}")
    step, scopes = tiled["step_ms"], tiled["by_scope"]
    lines = [f"{part_lib.STEP_PROGRAM}: module {report['module']}, "
             f"{tiled['steps']:g} steps a device on "
             f"{len(trace.device_planes())} devices, {step:.2f} ms a step",
             f"{'part':<28}{'ms a step':>11}{'share':>9}{'calls':>9}"]

    def row(label, ms, calls=None):
        lines.append(f"{label:<28}{ms:>11.2f}{100 * ms / step:>8.1f}%"
                     + ("" if calls is None else f"{calls:>9.0f}"))

    for bucket in part_lib.BUCKETS:
        if not tiled["calls"][bucket]:
            continue
        row(bucket, tiled["parts"][bucket], tiled["calls"][bucket])
        under = sorted(((sc, v) for (b, sc), v in scopes.items()
                        if b == bucket and sc), key=lambda kv: -kv[1]["ms"])
        if len(under) > 1 or (under and under[0][0] != bucket):
            for sc, v in under:
                row("  " + sc, v["ms"], v["calls"])
    row(part_lib.IDLE, tiled[part_lib.IDLE])
    row("sum", sum(tiled["parts"].values()) + tiled[part_lib.IDLE])
    lines.append(f"bare copies, transposes and converts (inside the parts): "
                 f"{tiled['bare_copy_ms']:.2f} ms a step; operations the "
                 f"report has no row for: {tiled['unjoined_ms']:.2f}")
    for sig, v in sorted(tiled["unscoped_ops"].items(),
                         key=lambda kv: -kv[1]["ms"])[:top]:
        lines.append(f"  unscoped: {v['ms']:8.2f} ms  {v['calls']:5.0f} x  "
                     f"{sig}")
    mem, limit = report.get("memory"), report.get("bytes_limit")
    if mem:
        gib = 2.0 ** 30
        lines.append(
            f"memory a chip: {mem['total_bytes'] / gib:.3f} GiB"
            + (f" of {limit / gib:.2f} ({100 * mem['total_bytes'] / limit:.1f}"
               f" %)" if limit else "")
            + f" = arguments {mem['argument_bytes'] / gib:.3f} + outputs "
            f"{mem['output_bytes'] / gib:.3f} - aliased "
            f"{mem['alias_bytes'] / gib:.3f} + temporaries "
            f"{mem['temp_bytes'] / gib:.3f}; the report took "
            f"{report.get('seconds', 0.0):.1f} s")
    return "\n".join(lines)


STEP_FLAG_NAMES = ((session.STEP_PROFILED, "profiled"),
                   (session.STEP_SYNCED, "synced"),
                   (session.STEP_DEVICE_DRY, "device_dry"))
# a step is named in the table where its wall is over this many medians
SLOW_STEP = 1.25
STEP_ANNOTATIONS = (ANNOTATION_PREFIX + "train.step",
                    ANNOTATION_PREFIX + "train.report")


def _union(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _within(merged: List[Tuple[float, float]], lo: float, hi: float
            ) -> float:
    """Seconds of the merged intervals that lie in [lo, hi]."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def step_walls(rows: List[list], compiles: List[Tuple[float, float]]
               ) -> List[Dict[str, Any]]:
    """One record an interval of the ledger (a row to the next): the
    wall, and its split.  Compile seconds are the union of the worker's
    `xla.compile` spans inside the interval, taken out of dispatch where
    they lie in the step's block and out of the loop's own elsewhere, so
    that the four columns sum to the wall."""
    merged = _union(compiles)
    out = []
    for (step, t, dispatch, report, flags), nxt in zip(rows, rows[1:]):
        wall = nxt[1] - t
        in_block = _within(merged, t, min(t + dispatch, nxt[1]))
        outside = _within(merged, t, nxt[1]) - in_block
        out.append({"step": step, "t_enter": t, "wall": wall, "flags": flags,
                    "dispatch": dispatch - in_block, "report": report,
                    "compile": in_block + outside,
                    "own": wall - dispatch - report - outside})
    return out


def idle_by_annotation(planes: Dict[str, Dict[str, list]], offset_s: float,
                       walls: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """For each interval of `walls` the profile covers: the seconds the
    first device ran no program in it, each gap put down to the program's
    annotation (`STEP_ANNOTATIONS`) that covers its midpoint, else to
    "neither"; all on the epoch clock (`clock_offset`)."""
    device = sorted(p for p in planes if _DEVICE_PLANE.match(p))
    if not device:
        return []
    busy = _union([(s / 1e9 + offset_s, (s + d) / 1e9 + offset_s)
                   for evs in planes[device[0]].values() for _, s, d, _ in evs])
    events = [(s / 1e9 + offset_s, (s + d) / 1e9 + offset_s, name)
              for lines in planes.values() for evs in lines.values()
              for name, s, d, _ in evs]
    seen = (min(e[0] for e in events), max(e[1] for e in events))
    notes = [e for e in events if e[2] in STEP_ANNOTATIONS]
    out = []
    for w in walls:
        lo, hi = w["t_enter"], w["t_enter"] + w["wall"]
        if lo < seen[0] or hi > seen[1]:
            continue        # not wholly inside what the profile saw
        idle = dict.fromkeys(STEP_ANNOTATIONS + ("neither",), 0.0)
        edges = [lo] + [x for a, b in busy if b > lo and a < hi
                        for x in (max(a, lo), min(b, hi))] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            mid = (a + b) / 2
            idle[next((n for s, e, n in notes if s <= mid <= e),
                      "neither")] += b - a
        out.append({"step": w["step"], "wall": w["wall"], "idle": idle})
    return out


def steps_table(timeline: str, planes: Optional[dict] = None,
                offset_s: Optional[float] = None) -> str:
    """A run's step ledger as text, a block a rank (module docstring)."""
    with open(timeline) as f:
        doc = json.load(f)
    if not doc.get("steps"):
        raise SystemExit(f"{timeline} holds no step ledger: the run's "
                         "program wrote none (an earlier commit)")
    ms = 1e3
    lines: List[str] = []
    for rank, led in sorted(doc["steps"].items()):
        rows, tot = led["rows"], led["totals"]
        walls = step_walls(rows, [
            (c["start"], c["end"]) for c in doc.get("compiles", [])
            if c.get("worker") == rank])
        lines.append(
            f"{rank}: {tot['steps']} steps ({len(rows)} rows kept, "
            f"{led['dropped']} dropped); all steps: wall "
            f"{tot['wall_s']:.3f} s, dispatch {tot['dispatch_s']:.3f}, "
            f"report {tot['report_s']:.3f}, longest "
            f"{tot['longest_wall_s'] * ms:.2f} ms at step "
            f"{tot['longest_wall_step']}")
        if len(walls) < 4:
            continue
        w = [x["wall"] for x in walls]
        median = statistics.median(w)
        p99 = statistics.quantiles(w, n=100, method="inclusive")[98]
        quarter = len(w) // 4
        lines.append(
            f"  wall of a step, ms: median {median * ms:.2f}, p99 "
            f"{p99 * ms:.2f}, longest {max(w) * ms:.2f}; "
            f"first quarter's median "
            f"{statistics.median(w[:quarter]) * ms:.2f}, last quarter's "
            f"{statistics.median(w[-quarter:]) * ms:.2f}")
        slow = [x for x in walls if x["wall"] > SLOW_STEP * median]
        lines.append(f"  {len(slow)} steps over {SLOW_STEP} x the median"
                     + (" (ms):" if slow else ""))
        if slow:
            lines.append(f"  {'step':>8}{'wall':>10}{'dispatch':>10}"
                         f"{'report':>10}{'compile':>10}{'own':>10}  flags")
        for x in slow:
            flags = ",".join(n for bit, n in STEP_FLAG_NAMES
                             if x["flags"] & bit)
            lines.append(
                f"  {x['step']:>8}" + "".join(
                    f"{x[k] * ms:>10.2f}" for k in
                    ("wall", "dispatch", "report", "compile", "own"))
                + f"  {flags or '-'}")
        if planes and offset_s is not None:
            traced = idle_by_annotation(planes, offset_s, walls)
            lines.append(f"  {len(traced)} steps inside the profile; the "
                         "first device's idle time, ms, by the program's "
                         "annotation over the gap's midpoint:")
            if traced:
                lines.append(f"  {'step':>8}{'wall':>10}{'train.step':>12}"
                             f"{'train.report':>14}{'neither':>10}")
            for x in traced:
                lines.append(
                    f"  {x['step']:>8}{x['wall'] * ms:>10.2f}"
                    f"{x['idle'][STEP_ANNOTATIONS[0]] * ms:>12.3f}"
                    f"{x['idle'][STEP_ANNOTATIONS[1]] * ms:>14.3f}"
                    f"{x['idle']['neither'] * ms:>10.3f}")
    return "\n".join(lines)


def dump_stats(directory: str) -> Dict[str, Any]:
    out: Dict[str, Any] = {"dir": directory}
    for stream in STREAMS:
        segs = journal.list_segments(directory, stream)
        envs = journal.replay(directory, stream)
        out[stream] = {
            "segments": len(segs),
            "bytes": sum(size for _, _, _, size in segs),
            "records": len(envs),
            "first_ts": envs[0]["t"] if envs else 0.0,
            "last_ts": envs[-1]["t"] if envs else 0.0,
        }
    return out


def build_trace(directory: str, since: float = 0.0,
                until: float = 0.0,
                streams=STREAMS) -> List[Dict[str, Any]]:
    events: List[Dict[str, Any]] = []
    if "spans" in streams:
        events.extend(span_events(
            journal.replay(directory, "spans", since=since,
                           until=until)))
    if "flight" in streams:
        events.extend(flight_events(
            journal.replay(directory, "flight", since=since,
                           until=until)))
    if "metrics" in streams:
        events.extend(metric_events(
            journal.replay(directory, "metrics", since=since,
                           until=until)))
    if "device" in streams:
        events.extend(device_events(
            journal.replay(directory, "device", since=since,
                           until=until)))
    return events


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Export a window of the ops journal as a chrome "
                    "trace (load in Perfetto / chrome://tracing).")
    ap.add_argument("--dir", default=os.environ.get(
        "RAY_TPU_OPS_JOURNAL_DIR", ""),
        help="journal directory (default: $RAY_TPU_OPS_JOURNAL_DIR)")
    ap.add_argument("--since", type=float, default=0.0,
                    help="window start (epoch seconds)")
    ap.add_argument("--until", type=float, default=0.0,
                    help="window end (epoch seconds)")
    ap.add_argument("--last", type=float, default=0.0,
                    help="shorthand: window = the last N seconds")
    ap.add_argument("--streams", default=",".join(STREAMS),
                    help="comma list of streams to include "
                         f"(default: {','.join(STREAMS)})")
    ap.add_argument("--out", default="",
                    help="output file (default: stdout)")
    ap.add_argument("--stats", action="store_true",
                    help="print per-stream segment/record counts (and a "
                         "profile's clock offset) instead of a trace")
    ap.add_argument("--xplane", default="",
                    help="a profiler trace (.xplane.pb) to lay beside "
                         "the spans, on the epoch clock")
    ap.add_argument("--timeline", default="",
                    help="a train run's timeline.json to add")
    ap.add_argument("--parts", action="store_true",
                    help="print the step program's time by the model's "
                         "parts (needs --xplane and --timeline of a "
                         "traced run) instead of a trace")
    ap.add_argument("--steps", action="store_true",
                    help="print the run's step ledger (needs --timeline; "
                         "with --xplane, each traced step's idle time on "
                         "the device by the program's annotation) instead "
                         "of a trace")
    args = ap.parse_args(argv)
    if args.parts:
        if not (args.xplane and args.timeline):
            ap.error("--parts needs --xplane and --timeline")
        print(parts_table(args.xplane, args.timeline))
        return 0
    if args.steps and not args.timeline:
        ap.error("--steps needs --timeline")
    if not (args.dir or args.xplane or args.timeline):
        ap.error("--dir required (or set RAY_TPU_OPS_JOURNAL_DIR), "
                 "unless --xplane or --timeline is given")
    since = args.since
    if args.last > 0:
        since = max(since, time.time() - args.last)
    planes, offset = {}, None
    if args.xplane:
        planes = read_xplane(args.xplane)
        offset = clock_offset(planes)
    if args.steps:
        print(steps_table(args.timeline, planes,
                          offset[0] if offset else None))
        return 0
    if args.stats:
        stats = dump_stats(args.dir) if args.dir else {}
        if args.xplane:
            stats["xplane"] = None if offset is None else {
                "clock_offset_s": offset[0],
                "offset_spread_ms": offset[1] * 1e3,
                "annotations": offset[2]}
        print(json.dumps(stats, indent=2))
        return 0
    streams = tuple(s.strip() for s in args.streams.split(",")
                    if s.strip())
    events = build_trace(args.dir, since=since, until=args.until,
                         streams=streams) if args.dir else []
    if args.xplane:
        if offset is None:
            print("no ray_tpu: annotation carries t_epoch: the profile "
                  "stays on its own clock", file=sys.stderr)
        events.extend(xplane_events(planes, offset[0] if offset else 0.0))
    if args.timeline:
        events.extend(timeline_events(args.timeline))
    payload = json.dumps({"traceEvents": events}, default=str)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(payload)
        print(f"wrote {len(events)} events -> {args.out}",
              file=sys.stderr)
    else:
        print(payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
