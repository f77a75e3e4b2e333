"""Head scale-envelope smoke.

The full probe (scripts/scale_probe.py: 50 nodes / 10k queued tasks /
1k actors / 100 PGs) runs out-of-band; this keeps the machinery exercised in the suite at CI-sized numbers —
many logical nodes, a queued-task burst bigger than the worker pool,
a batch of actors, and PG create/remove, all asserting completion.
"""

import json
import os
import subprocess
import sys


def test_scale_probe_small(tmp_path):
    out = str(tmp_path / "scale.json")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "scale_probe.py"),
         "--nodes", "20", "--tasks", "400", "--actors", "12",
         "--pgs", "15", "--out", out],
        capture_output=True, text=True, timeout=600, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.load(open(out))
    assert data["nodes"]["count"] == 20
    assert data["tasks"]["queued"] == 400
    assert data["tasks"]["drain_per_s"] > 0
    assert data["actors"]["count"] == 12
    assert data["placement_groups"]["count"] == 15
