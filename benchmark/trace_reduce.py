"""From a profiler trace (xplane) to the numbers the layer metrics read.

Only the process that holds the chip can trace it; it writes an
`.xplane.pb`, and this module reads that file back with nothing but JAX
(`jax.profiler.ProfileData`), in the driver process, without touching a
backend.  What it gives:

- device busy time (the union of the intervals in which an operation ran,
  per device, averaged over the devices) and the traced window;
- device time and call count of programs (the "XLA Modules" line) and of
  operations or kernels (the "XLA Ops" line), looked up by a regular
  expression that the LAYER-METRIC FILE supplies: no program or kernel
  name is written here;
- the self time of collective operations on the device's one stream of
  operations (time exposed: nothing else runs while a `-done` waits);
- the longest idle gaps, each named by the host annotation
  (`jax.profiler.TraceAnnotation`, prefix "bench:") that covered it.

A trace can be saved as a small JSON document (`to_json`) and loaded
again (`from_json`): the recorded trace under `benchmark/tests/data/` is
one, and the test of this reduction runs on it.
"""

from __future__ import annotations

import gzip
import json
import re
from typing import Dict, Iterable, List, Optional, Tuple

# (name, start_ns, duration_ns, detail): detail joins the event's string
# stats (long name, op name, category), where kernels' scopes show.
Event = Tuple[str, float, float, str]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# On a TPU an operation's event name is its whole HLO line, layouts and
# all: "%fusion.4 = bf16[5,2048]{1,0:T(8,128)(2,1)} fusion(...)".  The
# layouts are dropped and the text cut when a trace is loaded; what is
# left (name, result type, op kind, first operands) is what the readers'
# patterns match, since a Pallas kernel carries no name of its own there.
_LAYOUT = re.compile(r"\{[^{}]*\}")
_NAME_CHARS = 240
_SIGNATURE = re.compile(r"^%?[\w.\-]+ = (.*?) ([\w\-]+)\(")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DETAIL_KEYS = ("long_name", "tf_op", "hlo_op", "hlo_module", "name",
                "hlo_category", "kernel_details", "program_id")


def _merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class TraceView:
    def __init__(self, planes: Dict[str, Dict[str, List[Event]]],
                 t0_epoch: Optional[float] = None):
        self.planes = planes
        self.t0_epoch = t0_epoch
        self._self: Dict[str, list] = {}

    # -- structure ---------------------------------------------------------
    def device_planes(self) -> List[str]:
        return sorted(p for p in self.planes if DEVICE_PLANE.match(p))

    def host_events(self) -> Iterable[Event]:
        for p, lines in self.planes.items():
            if DEVICE_PLANE.match(p) or p.startswith("/device:"):
                continue
            for evs in lines.values():
                yield from evs

    def _line(self, plane: str, line: str) -> List[Event]:
        return self.planes.get(plane, {}).get(line, [])

    def span_ns(self) -> Tuple[float, float]:
        lo, hi = float("inf"), float("-inf")
        for lines in self.planes.values():
            for evs in lines.values():
                for _, s, d, _ in evs:
                    lo, hi = min(lo, s), max(hi, s + d)
        return (0.0, 0.0) if lo == float("inf") else (lo, hi)

    # -- busy and idle -----------------------------------------------------
    def busy_intervals(self, plane: str) -> List[List[float]]:
        return _merge((s, s + d) for _, s, d, _ in self._line(plane, OPS_LINE)
                      if d > 0)

    def busy(self) -> Dict[str, float]:
        """busy_s: seconds in which an operation ran, averaged over the
        devices in the trace; window_s: the traced window."""
        devs = self.device_planes()
        lo, hi = self.span_ns()
        per_dev = [sum(e - s for s, e in self.busy_intervals(p))
                   for p in devs]
        return {"busy_s": (sum(per_dev) / len(per_dev) / 1e9) if devs else 0.0,
                "window_s": (hi - lo) / 1e9, "devices": len(devs)}

    def idle_share(self) -> Optional[float]:
        b = self.busy()
        if not b["devices"] or b["window_s"] <= 0:
            return None
        return 100.0 * (1.0 - b["busy_s"] / b["window_s"])

    # -- programs and operations ------------------------------------------
    def _matching(self, line: str, pattern: str) -> Dict[str, float]:
        """Count and device seconds of the events on `line` whose name or
        detail matches, averaged over the devices."""
        rx = re.compile(pattern)
        devs = self.device_planes()
        n, total = 0, 0.0
        for p in devs:
            for name, _, d, detail in self._line(p, line):
                if rx.search(name) or (detail and rx.search(detail)):
                    n += 1
                    total += d
        k = max(1, len(devs))
        return {"count": n / k, "seconds": total / k / 1e9}

    def program_time(self, pattern: str) -> Dict[str, float]:
        return self._matching(MODULES_LINE, pattern)

    def op_time(self, pattern: str) -> Dict[str, float]:
        return self._matching(OPS_LINE, pattern)

    def self_times(self, plane: str) -> List[Tuple[str, float, float, float]]:
        """(name, start, duration, self) of every operation on a device:
        the "XLA Ops" line nests (a `while` covers the operations of its
        body), and an operation's self time is its duration less that of
        the operations directly inside it."""
        cached = self._self.get(plane)
        if cached is not None:
            return cached
        evs = sorted(((s, -d, n) for n, s, d, _ in
                      self._line(plane, OPS_LINE) if d > 0))
        out: List[List] = []
        stack: List[int] = []
        for s, nd, n in evs:
            d = -nd
            while stack and out[stack[-1]][1] + out[stack[-1]][2] <= s:
                stack.pop()
            if stack:
                out[stack[-1]][3] -= d
            out.append([n, s, d, d])
            stack.append(len(out) - 1)
        res = [(n, s, d, max(0.0, sf)) for n, s, d, sf in out]
        self._self[plane] = res
        return res

    def exposed_seconds(self, pattern: str) -> Dict[str, float]:
        """Self time of the operations matching `pattern` (the
        collectives: a `-done` that waits, or a synchronous collective)
        on the device's one stream of operations, where nothing else runs
        beside them: time exposed.  Averaged over the devices."""
        rx = re.compile(pattern)
        devs = self.device_planes()
        n, total = 0, 0.0
        for p in devs:
            for name, _, _, sf in self.self_times(p):
                if rx.search(name):
                    n += 1
                    total += sf
        k = max(1, len(devs))
        return {"count": n / k, "exposed_seconds": total / k / 1e9}

    # -- breakdown ---------------------------------------------------------
    @staticmethod
    def signature(name: str) -> str:
        """"custom-call bf16[64,32,64]" from an HLO line: op kind and
        result type, the same for one operation in every layer."""
        m = _SIGNATURE.match(name)
        return (f"{m.group(2)} {m.group(1)}" if m else name)[:96]

    def breakdown(self, host_prefix: str = "bench:", top: int = 10) -> dict:
        """The device operations that took most (self) time, grouped by
        signature and averaged over the devices, and the idle gaps of the
        first device summed by what the host was doing (the innermost
        host annotation over the gap's midpoint)."""
        devs = self.device_planes()
        by_op: Dict[str, float] = {}
        for p in devs:
            for name, _, _, sf in self.self_times(p):
                key = self.signature(name)
                by_op[key] = by_op.get(key, 0.0) + sf
        k = max(1, len(devs))
        device_ops = [[n, s / k / 1e9] for n, s in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]]
        gaps: Dict[str, float] = {}
        if devs:
            host = sorted(((s, s + d, n) for n, s, d, _ in self.host_events()
                           if n.startswith(host_prefix)),
                          key=lambda x: x[0])
            busy = self.busy_intervals(devs[0])
            lo, hi = self.span_ns()
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for i in range(0, len(edges), 2):
                s, e = edges[i], edges[i + 1]
                if e - s <= 0:
                    continue
                mid = (s + e) / 2.0
                cover = [(he - hs, n) for hs, he, n in host
                         if hs <= mid <= he]
                name = (min(cover)[1][len(host_prefix):] if cover
                        else "no_host_annotation")
                gaps[name] = gaps.get(name, 0.0) + (e - s)
        idle_gaps = [[n, s / 1e9] for n, s in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": device_ops, "idle_gaps": idle_gaps}

    # -- fixtures ----------------------------------------------------------
    def to_json(self, path: str, keep_host_prefix: str = "bench:") -> None:
        """Save the device planes whole and, of the host planes, only the
        benchmark's annotations: a recorded trace small enough to
        commit."""
        doc = {"t0_epoch": self.t0_epoch, "planes": {}}
        for p, lines in self.planes.items():
            dev = bool(DEVICE_PLANE.match(p))
            kept = {}
            for ln, evs in lines.items():
                sel = evs if dev else [e for e in evs
                                       if e[0].startswith(keep_host_prefix)]
                if sel:
                    kept[ln] = [list(e) for e in sel]
            if kept:
                doc["planes"][p] = kept
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump(doc, f)


def from_json(path: str) -> TraceView:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
              for p, lines in doc["planes"].items()}
    return TraceView(planes, doc.get("t0_epoch"))


def load_xplane(path: str, t0_epoch: Optional[float] = None) -> TraceView:
    """Read an `.xplane.pb` with jax.profiler.ProfileData.  Event times
    are nanoseconds on the profiler's own clock; `t0_epoch` (the host's
    clock just before `start_trace`) lets a caller place program spans
    beside them."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                detail = ""
                try:
                    parts = [str(v) for k, v in e.stats
                             if k in _DETAIL_KEYS and isinstance(v, str)]
                    detail = " | ".join(parts)[:300]
                except Exception:  # noqa: BLE001 — stats are optional
                    pass
                evs.append((_LAYOUT.sub("", e.name)[:_NAME_CHARS],
                            float(e.start_ns), float(e.duration_ns),
                            detail))
    return TraceView(planes, t0_epoch)
