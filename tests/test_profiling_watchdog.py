"""Cluster span harvest, per-worker resource profiling, and the
straggler/health watchdog (gcs._op_harvest_spans, worker profile
sampler, gcs._Watchdog), plus the static metrics-conformance check."""

import importlib.util
import json
import os
import signal
import time
import urllib.request

import ray_tpu
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Span-ring cursor math (tracing.collect_spans_since)
# ---------------------------------------------------------------------------

def _record_n(n, name="u"):
    for i in range(n):
        tracing.record_span(f"{name}{i}", 1.0 + i, 2.0 + i, force=True)


def test_collect_spans_since_incremental_and_partial():
    tracing.clear_spans()
    _record_n(10)
    out = tracing.collect_spans_since(0, max_spans=4)
    assert [r[3] for r in out["rows"]] == ["u0", "u1", "u2", "u3"]
    assert out["cursor"] == 4 and out["missed"] == 0
    out = tracing.collect_spans_since(out["cursor"], max_spans=100)
    assert len(out["rows"]) == 6 and out["cursor"] == 10
    # Caught up: empty read, cursor stable.
    out = tracing.collect_spans_since(out["cursor"])
    assert out["rows"] == [] and out["cursor"] == 10
    # New spans appear exactly once under the held cursor.
    _record_n(3, name="v")
    out = tracing.collect_spans_since(out["cursor"])
    assert [r[3] for r in out["rows"]] == ["v0", "v1", "v2"]
    tracing.clear_spans()


def test_collect_spans_since_reports_evictions_as_missed(monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACE_MAX_SPANS", "16")
    tracing.clear_spans()
    tracing.enable_tracing()  # re-reads the env -> resizes the ring
    try:
        _record_n(40)
        out = tracing.collect_spans_since(0, max_spans=100)
        # Ring kept the newest 16; the 24 evicted before our cursor-0
        # read are reported, not silently skipped.
        assert len(out["rows"]) == 16
        assert out["missed"] == 24
        assert out["cursor"] == 40
        assert out["rows"][0][3] == "u24"
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
        monkeypatch.delenv("RAY_TPU_TRACE_MAX_SPANS")
        tracing.enable_tracing()  # restore default ring capacity
        tracing.disable_tracing()


def test_collect_spans_since_heals_after_ring_clear():
    tracing.clear_spans()
    _record_n(5)
    cur = tracing.collect_spans_since(0)["cursor"]
    assert cur == 5
    tracing.clear_spans()  # worker restarted / ring reset: seq rewinds
    out = tracing.collect_spans_since(cur)
    assert out["rows"] == [] and out["cursor"] == 0
    _record_n(2)
    out = tracing.collect_spans_since(out["cursor"])
    assert len(out["rows"]) == 2
    tracing.clear_spans()


def test_span_row_to_dict_expansion():
    row = ["sid", "par", "tid", "nm", 1.0, 2.0, None]
    s = tracing.span_row_to_dict(row)
    assert s == {"span_id": "sid", "parent_id": "par", "trace_id": "tid",
                 "name": "nm", "start": 1.0, "end": 2.0,
                 "attributes": {}}
    # Head ingest extends rows with worker/pid in place.
    row += ["whex", 4242]
    s = tracing.span_row_to_dict(row)
    assert s["worker"] == "whex" and s["pid"] == 4242


# ---------------------------------------------------------------------------
# profile_report frames on the coalescing flusher
# ---------------------------------------------------------------------------

def test_head_frames_collapse_profile_report_run_to_newest():
    from ray_tpu.core.runtime import CoreClient

    items = [
        ("profile_report", {"ts": 1.0, "cpu_percent": 10.0}),
        ("profile_report", {"ts": 2.0, "cpu_percent": 20.0}),
        ("profile_report", {"ts": 3.0, "cpu_percent": 30.0}),
    ]
    frames = [msg for _, msg in CoreClient._head_frames(items)]
    # Point-in-time state: a backlogged run is ONE frame, newest wins.
    assert len(frames) == 1
    assert frames[0] == {"op": "profile_report",
                         "sample": {"ts": 3.0, "cpu_percent": 30.0}}


# ---------------------------------------------------------------------------
# End-to-end: harvest + profiling + dashboard surfaces
# ---------------------------------------------------------------------------

def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_harvest_profile_and_dashboard_surfaces():
    """Driver + workers in one cluster: worker execution spans are
    parent-linked to the driver's trace via shared trace ids, pulled
    through the head (collect_spans), and served by /api/trace,
    /api/spans and /api/profile."""
    rt = ray_tpu.init(num_cpus=4)
    try:
        tracing.enable_tracing()

        @ray_tpu.remote
        def inner(x):
            return x * 2

        @ray_tpu.remote
        def outer(x):
            return ray_tpu.get(inner.remote(x)) + 1

        with tracing.trace_span("e2e-root"):
            assert ray_tpu.get([outer.remote(i) for i in range(2)],
                               timeout=60) == [1, 3]
        local = tracing.get_spans()
        root = next(s for s in local if s["name"] == "e2e-root")
        trace_id = root["trace_id"]
        assert trace_id

        reply = rt.core.client.call(
            {"op": "harvest_spans", "timeout_s": 15.0})
        spans = reply["spans"]
        assert reply["workers_polled"] >= 2
        mine = [s for s in spans if s["trace_id"] == trace_id]
        # Worker-side execution spans joined the driver's trace.
        workers = {s["worker"] for s in mine
                   if s.get("worker")
                   and s["worker"] != rt.core.worker_hex}
        assert len(workers) >= 2, mine
        by_id = {s["span_id"]: s for s in mine}
        for s in local:
            by_id.setdefault(s["span_id"], s)
        # Parent links resolve inside the harvested trace up to the
        # driver's root.
        exec_spans = [s for s in mine if s.get("worker") in workers]
        assert exec_spans
        for s in exec_spans:
            assert s.get("pid"), s
            cur, hops = s, 0
            while cur.get("parent_id") and hops < 10:
                nxt = by_id.get(cur["parent_id"])
                if nxt is None:
                    break
                cur, hops = nxt, hops + 1
            assert cur["span_id"] == root["span_id"], s

        # Sampler: retune fast, then samples from every worker arrive.
        rt.core.client.call({"op": "set_profile_config",
                             "enabled": True, "interval_s": 0.2})
        deadline = time.time() + 20
        prof = {}
        while time.time() < deadline:
            prof = rt.core.client.call({"op": "get_profile"})
            if len(prof.get("workers", {})) >= 2:
                break
            time.sleep(0.3)
        assert len(prof["workers"]) >= 2, prof
        sample = next(iter(prof["workers"].values()))
        for key in ("cpu_percent", "rss_bytes", "queue_depth",
                    "arena_used_bytes", "mem_total_bytes"):
            assert key in sample, sample
        assert prof["watchdog"]["enabled"] is True

        from ray_tpu.dashboard.http_head import Dashboard
        dash = Dashboard(rt)
        try:
            ev = _get_json(f"{dash.url}/api/trace")
            pids = {e.get("pid") for e in ev
                    if e.get("ph") == "X" and e.get("pid", 0) > 3}
            assert pids, "no harvested worker span lanes in /api/trace"
            out = _get_json(
                f"{dash.url}/api/spans?trace_id={trace_id}")
            assert out["spans"]
            assert all(s["trace_id"] == trace_id for s in out["spans"])
            prof2 = _get_json(f"{dash.url}/api/profile")
            assert prof2["workers"]
        finally:
            dash.stop()
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Watchdog: stalled task -> health verdict + counter
# ---------------------------------------------------------------------------

class _WorkerStaller:
    """util/chaos.py-style killer whose `kill` is SIGSTOP: the victim
    worker freezes mid-task (a stall, not a crash)."""

    def __init__(self, pidfile):
        from ray_tpu.util.chaos import ResourceKiller

        outer = self

        class Staller(ResourceKiller):
            def find_target(self):
                try:
                    with open(pidfile) as f:
                        return int(f.read().strip())
                except (OSError, ValueError):
                    return None

            def kill(self, pid):
                os.kill(pid, signal.SIGSTOP)
                outer.stalled = pid
                return True

        self.stalled = None
        self._killer = Staller(interval_s=0.1, max_kills=1)

    def start(self):
        self._killer.start()
        return self

    def stop(self):
        self._killer.stop()
        if self.stalled is not None:
            try:
                os.kill(self.stalled, signal.SIGCONT)
            except OSError:
                pass


def test_watchdog_flags_stalled_task(tmp_path, monkeypatch):
    from ray_tpu.util import flight_recorder

    monkeypatch.setenv("RAY_TPU_WATCHDOG_INTERVAL_S", "0.3")
    monkeypatch.setenv("RAY_TPU_WATCHDOG_MIN_SAMPLES", "3")
    monkeypatch.setenv("RAY_TPU_WATCHDOG_MULTIPLIER", "1.5")
    monkeypatch.setenv("RAY_TPU_WATCHDOG_MIN_AGE_S", "0.4")
    pidfile = str(tmp_path / "victim.pid")
    stopfile = str(tmp_path / "victim.stop")
    rt = ray_tpu.init(num_cpus=4)
    staller = _WorkerStaller(pidfile)
    try:
        wd = rt.control._watchdog
        assert wd is not None and wd.interval_s == 0.3

        @ray_tpu.remote
        def work(pid_path, stop_path):
            if not pid_path:
                return os.getpid()
            with open(pid_path, "w") as f:
                f.write(str(os.getpid()))
            for _ in range(600):  # stalls under SIGSTOP; exits fast
                if os.path.exists(stop_path):
                    return os.getpid()
                time.sleep(0.05)
            return os.getpid()

        # Fast siblings build the completed-duration distribution.
        ray_tpu.get([work.remote("", "") for _ in range(5)], timeout=60)
        t_victim = time.time()
        victim = work.remote(pidfile, stopfile)

        # The worker tells the head "RUNNING" through its coalescing
        # flusher; a SIGSTOP that lands first leaves the head with no
        # running task to flag.  Freeze the worker only once it has.
        def head_sees_victim_running():
            with rt.control.lock:
                return any(
                    rec.state == "RUNNING" and rec.started_at >= t_victim
                    and (rec.spec.name or "").endswith("work")
                    for _, rec in rt.control.tasks.items())

        deadline = time.time() + 30
        while not head_sees_victim_running():
            assert time.time() < deadline, "victim never reported RUNNING"
            time.sleep(0.05)
        staller.start()

        deadline = time.time() + 30
        while time.time() < deadline and wd.stragglers_flagged == 0:
            time.sleep(0.2)
        assert wd.stragglers_flagged >= 1, wd.snapshot()
        health = [e for e in flight_recorder.dump()
                  if e.get("category") == "health"
                  and e.get("event") == "straggler"]
        assert health, "no health-lane straggler event recorded"
        assert health[0]["name"].endswith("work")
        snap = next(s for s in metrics_mod.local_snapshots()
                    if s["name"] == "ray_tpu_stragglers_total")
        assert sum(snap["series"].values()) >= 1.0

        staller.stop()  # SIGCONT -> victim sees stopfile and finishes
        with open(stopfile, "w") as f:
            f.write("stop")
        assert ray_tpu.get(victim, timeout=60) == staller.stalled
    finally:
        staller.stop()
        ray_tpu.shutdown()


def test_watchdog_off_switch_removes_detector(monkeypatch):
    monkeypatch.setenv("RAY_TPU_WATCHDOG", "0")
    rt = ray_tpu.init(num_cpus=1)
    try:
        # The scheduling loop's only residue is a None check.
        assert rt.control._watchdog is None
        reply = rt.core.client.call({"op": "get_profile"})
        assert reply["watchdog"] == {"enabled": False}
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Off-head flight recorder: the dashboard merges the head's ring
# ---------------------------------------------------------------------------

def test_flight_recorder_off_head_merge(tmp_path):
    import subprocess
    import sys

    from ray_tpu.core import rpc

    port = 24600 + (os.getpid() % 2000)
    env = dict(os.environ)
    env["RAY_TPU_CONTROL_PORT"] = str(port)
    env["RAY_TPU_GCS_STORE_PATH"] = str(tmp_path / "gcs.journal")
    head = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "start", "--head",
         "--num-cpus", "2", "--no-dashboard", "--block"],
        cwd=_REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 45
        while time.time() < deadline:
            try:
                c = rpc.Client(f"127.0.0.1:{port}", connect_timeout=1.0)
                c.call({"op": "ping"}, timeout=3.0)
                c.close()
                break
            except Exception:
                time.sleep(0.3)
        else:
            raise AssertionError("head never came up")
        rt = ray_tpu.init(address=f"127.0.0.1:{port}")
        try:
            assert getattr(rt, "control", None) is None  # off-head

            @ray_tpu.remote
            def ping():
                return 1

            assert ray_tpu.get(ping.remote(), timeout=60) == 1
            # A driver-side event that does not depend on two messages
            # happening to share a frame ("wire" is otherwise recorded
            # only when a batch of more than one is flushed).
            from ray_tpu.util import flight_recorder
            flight_recorder.record("wire", "driver_marker")
            from ray_tpu.dashboard.http_head import Dashboard
            dash = Dashboard(rt)
            try:
                out = _get_json(f"{dash.url}/api/flight_recorder")
                # Local ring stats AND the head process's ring, merged.
                assert "head_stats" in out, out.get("stats")
                assert out["head_stats"]["enabled"] is True
                cats = {e.get("category") for e in out["events"]}
                # Scheduler events only exist head-side; wire events
                # only driver-side — both present proves the merge.
                assert "scheduler" in cats and "wire" in cats, cats
            finally:
                dash.stop()
        finally:
            ray_tpu.shutdown()
    finally:
        head.terminate()
        try:
            head.wait(timeout=10)
        except subprocess.TimeoutExpired:
            head.kill()


# ---------------------------------------------------------------------------
# Static metrics conformance
# ---------------------------------------------------------------------------


def test_metrics_conformance_static_check():
    """Every ray_tpu_* metric referenced in tests/README is registered
    in the source, and every registered one is documented in README."""
    path = os.path.join(_REPO, "scripts", "check_metrics_conformance.py")
    spec = importlib.util.spec_from_file_location(
        "check_metrics_conformance", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    problems = mod.check()
    assert not problems, "\n".join(problems)
