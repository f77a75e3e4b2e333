#!/usr/bin/env python
"""bench_index — fold the repo-root bench JSONs into one trajectory.

Every PR that lands a perf-relevant change commits a bench JSON at the
repo root (BENCH_r*, DECODE_BENCH_r*, PROF_BENCH, ...), which makes
the perf trajectory unreadable as a series: ~30 files, each with its
own shape.  This script extracts every headline metric — any node with
a "metric"/"value" pair, any paired-phase "overhead" row, and the
pass/fail multichip probes — into one BENCH_TRAJECTORY.json of
{metric, value, source} rows.

    python scripts/bench_index.py            # writes BENCH_TRAJECTORY.json
    python scripts/bench_index.py --stdout   # print instead

tests/test_bench_index.py pins that every known bench file parses and
that its headline rows survive extraction, so a future bench that
breaks the shape fails the suite instead of silently dropping out of
the trajectory.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Everything bench-shaped the repo root accumulates.  MULTICHIP/SCALE
# predate the *_BENCH naming and are folded in explicitly.  A name ends
# in BENCH or BENCH_r<n>: BENCHMARK.json is the benchmark's contract,
# not a result.
PATTERNS = ("*BENCH.json", "*BENCH_r*.json", "MULTICHIP_r*.json",
            "SCALE_r*.json")

_ROUND_RE = re.compile(r"_r(\d+)\.json$")

# Numeric leaves that are headline metrics wherever they appear:
# throughputs, MFU, roofline fractions, kernel speedups.
_HEADLINE_LEAF_RE = re.compile(
    r"(^|_)(ops_s|ops_per_s|per_s|per_sec|per_sec_per_chip|mfu"
    r"|roofline_fraction|speedup_[a-z_]+)$")


def bench_files(root: str = REPO_ROOT) -> List[str]:
    found = set()
    for pat in PATTERNS:
        found.update(glob.glob(os.path.join(root, pat)))
    # The output of this script is not an input to it.
    found.discard(os.path.join(root, "BENCH_TRAJECTORY.json"))
    return sorted(found)


def _round_of(filename: str) -> Optional[int]:
    m = _ROUND_RE.search(filename)
    return int(m.group(1)) if m else None


def _walk(node: Any, path: str, rows: List[Dict[str, Any]],
          source: str) -> None:
    if isinstance(node, dict):
        metric = node.get("metric")
        value = node.get("value")
        if isinstance(metric, str) and isinstance(value, (int, float)) \
                and not isinstance(value, bool):
            rows.append({"metric": metric, "value": value,
                         "unit": node.get("unit"), "path": path,
                         "source": source})
        overhead = node.get("overhead")
        if isinstance(overhead, (int, float)) \
                and not isinstance(overhead, bool) and path:
            rows.append({"metric": f"{path}.overhead",
                         "value": overhead, "unit": "fraction",
                         "path": path, "source": source})
        for k, v in node.items():
            if k in ("metric", "value", "unit"):
                continue
            sub = f"{path}.{k}" if path else str(k)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and _HEADLINE_LEAF_RE.search(k):
                rows.append({"metric": sub, "value": v, "unit": None,
                             "path": path, "source": source})
            _walk(v, sub, rows, source)
    elif isinstance(node, list):
        for i, v in enumerate(node):
            _walk(v, f"{path}[{i}]", rows, source)


def _numeric_leaves(node: Any, path: str, out: List[tuple],
                    limit: int = 16) -> None:
    if len(out) >= limit:
        return
    if isinstance(node, dict):
        for k, v in node.items():
            _numeric_leaves(v, f"{path}.{k}" if path else str(k),
                            out, limit)
    elif isinstance(node, list):
        for i, v in enumerate(node):
            _numeric_leaves(v, f"{path}[{i}]", out, limit)
    elif isinstance(node, (int, float)) and not isinstance(node, bool):
        out.append((path, float(node)))


def extract_rows(doc: Any, source: str) -> List[Dict[str, Any]]:
    """Headline rows of one parsed bench document."""
    rows: List[Dict[str, Any]] = []
    _walk(doc, "", rows, source)
    if not rows:
        # No recognized headline shape (older probe files): keep the
        # file in the trajectory via its first numeric leaves rather
        # than silently dropping it.
        leaves: List[tuple] = []
        _numeric_leaves(doc, "", leaves)
        rows = [{"metric": p, "value": v, "unit": None, "path": p,
                 "source": source} for p, v in leaves]
    if isinstance(doc, dict) and isinstance(doc.get("ok"), bool):
        # Pass/fail probes (MULTICHIP): 1.0/0.0 so they plot.
        rows.append({"metric": "ok", "value": 1.0 if doc["ok"] else 0.0,
                     "unit": "bool", "path": "", "source": source})
    rnd = _round_of(source)
    if rnd is not None:
        for r in rows:
            r["round"] = rnd
    return rows


def build_index(root: str = REPO_ROOT) -> Dict[str, Any]:
    """Parse every bench file under `root` (raises on a file that does
    not parse — the test pins this) and fold the headline rows."""
    files = bench_files(root)
    rows: List[Dict[str, Any]] = []
    sources: List[str] = []
    for path in files:
        name = os.path.basename(path)
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)  # a broken bench file is a failure
        sources.append(name)
        rows.extend(extract_rows(doc, name))
    rows.sort(key=lambda r: (r["metric"], r.get("round") or -1,
                             r["source"]))
    return {"files": sources, "file_count": len(sources),
            "row_count": len(rows), "rows": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fold repo-root bench JSONs into "
                    "BENCH_TRAJECTORY.json.")
    ap.add_argument("--root", default=REPO_ROOT)
    ap.add_argument("--out", default="BENCH_TRAJECTORY.json",
                    help="output filename, relative to --root")
    ap.add_argument("--stdout", action="store_true",
                    help="print the index instead of writing it")
    args = ap.parse_args(argv)
    index = build_index(args.root)
    payload = json.dumps(index, indent=1, sort_keys=False)
    if args.stdout:
        print(payload)
        return 0
    out = os.path.join(args.root, args.out)
    with open(out, "w", encoding="utf-8") as fh:
        fh.write(payload + "\n")
    print(f"{index['row_count']} rows from {index['file_count']} "
          f"files -> {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
