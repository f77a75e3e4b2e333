"""Helpers the layer-metric readers share.  A reader is one small file
under `layer_metrics/` that declares NAME, UNIT, LAYER, MOVES, SOURCE and
WORKLOADS (the same values its BENCHMARK.json entry carries) and a

    read(spans, trace, counters, cell) -> float | None

`spans` are the program's spans harvested from every worker, `trace` is a
`trace_reduce.TraceView` (or None), `counters` is what the driver counted,
`cell` is the resolved cell (its `cell`, `config` and `mix`).  A reader
that finds nothing to read returns None and its metric is left out.
The names of programs and kernels in the trace are DATA in the reader's
file, not code here.
"""

from __future__ import annotations

from typing import Optional

from benchmark import arith
from benchmark.harness import percentile


def program_ms_per_call(trace, pattern: str, per_call: float = 1.0
                        ) -> Optional[float]:
    if trace is None:
        return None
    t = trace.program_time(pattern)
    if t["count"] <= 0:
        return None
    return t["seconds"] * 1e3 / (t["count"] * per_call)


def idle_share(trace) -> Optional[float]:
    return None if trace is None else trace.idle_share()


def window_compiles(counters: dict) -> Optional[float]:
    v = counters.get("window_compiles")
    return None if v is None else float(v)


def peak(counters: dict, key: str) -> float:
    return float(arith.peaks(counters["device"]["kind"])[key])


__all__ = ["arith", "percentile", "program_ms_per_call", "idle_share",
           "window_compiles", "peak"]
