"""What the start-up readers share: the run's `timeline.json`.

`JaxTrainer.fit` writes `<run_dir>/timeline.json` when the loop has ended
(ray_tpu/train/trainer.py): the start-up phases of the driver and of each
worker and the train programs' first spans, as span rows with `worker`
("driver", "rank0", ...) and `pid`; every `xla.compile` event; each
process's compile totals.  The train driver hands the readers no spans, so
they read that file, from where the driver put the run:
`.bench_out/train/<cell>/`.

A file that an EARLIER run left is no reading of this run: `load` gives
None unless the file's `startup.process` began after this process did
(`T_PROCESS_START` of `benchmark/run.py`, less the 5 s the OS's process
start time may lag the interpreter's first line by).  A program that
writes no such file (an earlier commit) gives None too.
"""

from __future__ import annotations

import json
import os
import sys
from typing import List, Optional

from benchmark import harness

STALE_SLACK_S = 5.0


def load(cell: dict) -> Optional[dict]:
    path = os.path.join(harness.OUT_DIR, "train", cell["cell"]["name"],
                        "timeline.json")
    t_run = getattr(sys.modules.get("__main__"), "T_PROCESS_START", None)
    if t_run is None or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    began = spans(doc, "startup.process", "driver")
    if not began or began[0]["start"] <= t_run - STALE_SLACK_S:
        return None
    return doc


def spans(doc: dict, name: str, worker: str) -> List[dict]:
    """The `worker`'s spans of that name, oldest first."""
    return sorted((s for s in doc.get("spans", [])
                   if s["name"] == name and s.get("worker") == worker),
                  key=lambda s: s["start"])


def first_duration(doc: Optional[dict], name: str,
                   worker: str = "rank0") -> Optional[float]:
    found = spans(doc, name, worker) if doc else []
    return found[0]["end"] - found[0]["start"] if found else None


def between(doc: Optional[dict], start: tuple, end: tuple, edge: str
            ) -> Optional[float]:
    """Seconds from the start of the first span `start` = (name, worker)
    to the `edge` ("start" or "end") of the first span `end`."""
    if not doc:
        return None
    a, b = spans(doc, *start), spans(doc, *end)
    return b[0][edge] - a[0]["start"] if a and b else None


def compile_totals(doc: Optional[dict], worker: str = "rank0"
                   ) -> Optional[dict]:
    return (doc or {}).get("compile_totals", {}).get(worker)


def host_median_ms(trace, name: str) -> Optional[float]:
    """Median duration of the host annotation `name` in the traced
    window (the program's spans enter the profile as "ray_tpu:<span>")."""
    if trace is None:
        return None
    durs = [d / 1e6 for n, _, d, _ in trace.host_events() if n == name]
    return harness.percentile(durs, 50)
