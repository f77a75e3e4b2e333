"""Durable ops journal (util/journal.py) and the always-on ops plane
built on it: segment rotation/retention, kill -9 truncated-tail crash
recovery, head-restart rehydration of the span store and flight
recorder, /api/profile history rings, the watchdog's arg-size-aware
straggler baselines, and the opsdump exporter.

The acceptance bar for the restart path is deliberately brutal: a
SIGKILLed head, restarted on the same journal dir, must serve its
pre-kill spans and flight events over the wire ops the dashboard uses
(`harvest_spans` with poll=False / `flight_recorder` with since=...).
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PORT = 25700 + (os.getpid() % 800)  # disjoint from test_head_restart's range

from ray_tpu.util import journal  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_streams():
    """Each test gets fresh shared streams and no inherited env gate."""
    journal.reset()
    yield
    journal.reset()


# ---------------------------------------------------------------------------
# Core write/replay
# ---------------------------------------------------------------------------

def test_append_replay_roundtrip_and_stats(tmp_path):
    j = journal.Journal(str(tmp_path), "t", fsync_s=0.02)
    try:
        for i in range(250):
            j.append({"i": i})
        assert j.flush(timeout=10)
        st = j.stats()
        assert st["appended"] == 250 and st["written"] == 250
        assert st["pending"] == 0 and st["dropped"] == 0
        assert st["segments"] >= 1 and st["bytes"] > 0
    finally:
        j.close()
    envs = journal.replay(str(tmp_path), "t")
    assert [e["d"]["i"] for e in envs] == list(range(250))
    # Envelope carries the writer pid and an append timestamp.
    assert all(e["p"] == os.getpid() and e["t"] > 0 for e in envs)
    # Window filters.
    mid = envs[100]["t"]
    late = journal.replay(str(tmp_path), "t", since=mid)
    assert late and all(e["t"] >= mid for e in late)
    assert len(journal.replay(str(tmp_path), "t", max_records=7)) == 7


def test_rotation_and_retention_bound_disk(tmp_path):
    # Tiny age-based rotation -> many segments; retention then holds
    # the stream under max_bytes while never deleting the live tail.
    j = journal.Journal(str(tmp_path), "r", max_bytes=4096,
                        rotate_s=0.01, fsync_s=0.01)
    try:
        for burst in range(30):
            for i in range(20):
                j.append({"burst": burst, "i": i, "pad": "x" * 40})
            assert j.flush(timeout=10)
            time.sleep(0.015)  # age out the open segment
        segs = journal.list_segments(str(tmp_path), "r")
        assert len(segs) > 1
        total = sum(size for _, _, _, size in segs)
        assert total <= 4096 + j.segment_bytes
        # Oldest records were reclaimed, newest survived.
        envs = journal.replay(str(tmp_path), "r")
        assert envs
        assert envs[-1]["d"]["burst"] == 29
        assert envs[0]["d"]["burst"] > 0
    finally:
        j.close()


def test_truncated_and_corrupt_tail_tolerated(tmp_path):
    j = journal.Journal(str(tmp_path), "c", fsync_s=0.01)
    try:
        for i in range(100):
            j.append(i)
        assert j.flush(timeout=10)
    finally:
        j.close()
    path = journal.list_segments(str(tmp_path), "c")[-1][0]
    with open(path, "ab") as f:
        f.write(b'0000001f {"t": 1, "p"')  # torn mid-payload
    assert [e["d"] for e in journal.replay(str(tmp_path), "c")] \
        == list(range(100))
    with open(path, "ab") as f:
        f.write(b"ZZZZZZZZ garbage\n")  # corrupt length prefix
    assert len(journal.replay(str(tmp_path), "c")) == 100


def test_sigkill_mid_write_recovers(tmp_path):
    """A writer process SIGKILLed between appends (chaos.PidfileKiller)
    loses at most its torn tail record; every complete record before
    the kill replays, and a successor process appends cleanly to the
    same stream."""
    from ray_tpu.util.chaos import PidfileKiller

    pidfile = str(tmp_path / "writer.pid")
    script = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        from ray_tpu.util import journal
        j = journal.Journal({str(tmp_path)!r}, "crash", fsync_s=0.005)
        for i in range(100):
            j.append({{"i": i, "pad": "y" * 64}})
        # Offer the pid only once something is on disk: on a busy host
        # the kill otherwise lands before the writer thread's first drain.
        assert j.flush(30.0)
        with open({pidfile!r}, "w") as f:
            f.write(str(os.getpid()))
        i = 100
        while True:
            j.append({{"i": i, "pad": "y" * 64}})
            i += 1
            if i % 50 == 0:
                time.sleep(0.001)
    """)
    proc = subprocess.Popen([sys.executable, "-c", script], cwd=REPO)
    killer = PidfileKiller(pidfile, sig=signal.SIGKILL,
                           warmup_s=0.5).start()
    try:
        assert proc.wait(timeout=30) == -signal.SIGKILL
    finally:
        killer.stop()
        if proc.poll() is None:
            proc.kill()
    envs = journal.replay(str(tmp_path), "crash")
    assert envs, "no records survived the kill"
    seq = [e["d"]["i"] for e in envs]
    # A length-prefixed stream can only lose the tail: what replays is
    # a gapless prefix of what was appended.
    assert seq == list(range(len(seq)))
    # The stream is still writable after the crash (new pid, new seq).
    j2 = journal.Journal(str(tmp_path), "crash", fsync_s=0.01)
    try:
        j2.append({"i": "post-crash"})
        assert j2.flush(timeout=10)
    finally:
        j2.close()
    assert journal.replay(str(tmp_path), "crash")[-1]["d"]["i"] \
        == "post-crash"


def test_stream_gated_on_env(tmp_path, monkeypatch):
    monkeypatch.delenv("RAY_TPU_OPS_JOURNAL_DIR", raising=False)
    assert journal.stream("spans") is None
    monkeypatch.setenv("RAY_TPU_OPS_JOURNAL_DIR", str(tmp_path))
    j = journal.stream("spans")
    assert j is not None
    assert journal.stream("spans") is j  # per-process singleton
    j.append([1, 2, 3])
    journal.flush_all(timeout=10)
    assert journal.replay(str(tmp_path), "spans")[0]["d"] == [1, 2, 3]


# ---------------------------------------------------------------------------
# Flight-recorder + metrics spill and rehydration
# ---------------------------------------------------------------------------

def test_flight_recorder_spill_since_and_rehydrate(tmp_path, monkeypatch):
    from ray_tpu.util import flight_recorder

    monkeypatch.setenv("RAY_TPU_OPS_JOURNAL_DIR", str(tmp_path))
    flight_recorder.configure(capacity=64)
    flight_recorder.clear()
    try:
        for i in range(10):
            flight_recorder.record("test", "ev", i=i)
        mid_ts = flight_recorder.dump()[5]["ts"]
        assert len(flight_recorder.dump(since=mid_ts)) == 5
        journal.flush_all(timeout=10)
        # Simulate the restart: ring wiped, journal intact.
        flight_recorder.clear()
        assert flight_recorder.dump() == []
        restored = flight_recorder.rehydrate()
        assert restored == 10
        events = flight_recorder.dump()
        assert [e["i"] for e in events] == list(range(10))
        # Idempotent: a second rehydrate adds nothing.
        assert flight_recorder.rehydrate() == 0
    finally:
        flight_recorder.configure()
        flight_recorder.clear()


def test_metrics_snapshots_journal_roundtrip(tmp_path, monkeypatch):
    from ray_tpu.util import metrics

    monkeypatch.setenv("RAY_TPU_OPS_JOURNAL_DIR", str(tmp_path))
    c = metrics.Counter("ops_journal_test_total", "test counter",
                        tag_keys=("k",))
    c.inc(2.0, tags={"k": "a"})
    metrics.publish_now()
    journal.flush_all(timeout=10)
    envs = journal.replay(str(tmp_path), "metrics")
    assert envs
    snaps = metrics.snapshots_from_json(envs[-1]["d"]["snapshots"])
    mine = next(s for s in snaps
                if s["name"] == "ops_journal_test_total")
    # Tuple-of-tuples series keys survive the JSON round trip.
    assert mine["series"][(("k", "a"),)] == 2.0


# ---------------------------------------------------------------------------
# Watchdog: arg-size-aware straggler baselines
# ---------------------------------------------------------------------------

def _mk_rec(name, state, dur=0.0, age=0.0, arg_bytes=-1, now=1000.0):
    from ray_tpu.core.gcs import TaskRecord

    spec = types.SimpleNamespace(name=name, func_id="f" * 8, args=())
    rec = TaskRecord(spec=spec, state=state, arg_bytes=arg_bytes)
    if state == "FINISHED":
        rec.started_at = now - 100.0
        rec.finished_at = rec.started_at + dur
    else:
        rec.started_at = now - age
    return rec


def test_watchdog_buckets_stragglers_by_arg_size(monkeypatch):
    """Mixed-size siblings: a small-input task judged against its own
    size class is flagged even though the pooled (size-blind)
    distribution — dominated by slow big-input siblings — would have
    hidden it; a big-input task inside its class's normal range is NOT
    flagged; and a size class without enough samples falls back to the
    pooled baseline."""
    from ray_tpu.core import gcs as gcs_mod
    from ray_tpu.util import flight_recorder

    monkeypatch.setenv("RAY_TPU_WATCHDOG_MIN_SAMPLES", "3")
    monkeypatch.setenv("RAY_TPU_WATCHDOG_MULTIPLIER", "2.0")
    monkeypatch.setenv("RAY_TPU_WATCHDOG_MIN_AGE_S", "0.05")

    srv = types.SimpleNamespace(
        lock=threading.Lock(), tasks={}, _m_stragglers=None,
        _profile_hist={}, workers={},
        _task_arg_bytes=lambda spec: 0)
    wd = gcs_mod._Watchdog(srv)
    now = 1000.0
    small, big = 1024, 1 << 30
    assert wd._size_bucket(small) != wd._size_bucket(big)
    assert wd._size_bucket(small) == wd._size_bucket(small // 2)
    # 4 fast small-input completions, 4 slow big-input completions.
    for i in range(4):
        srv.tasks[f"s{i}"] = _mk_rec("work", "FINISHED", dur=0.1,
                                     arg_bytes=small, now=now)
        srv.tasks[f"b{i}"] = _mk_rec("work", "FINISHED", dur=30.0,
                                     arg_bytes=big, now=now)
    # Small-input runner at 2s: 20x its class's p95, but well under
    # the pooled p95 (30s) — only the bucketed baseline catches it.
    srv.tasks["victim"] = _mk_rec("work", "RUNNING", age=2.0,
                                  arg_bytes=small, now=now)
    # Big-input runner at 10s: normal for its class.
    srv.tasks["bigok"] = _mk_rec("work", "RUNNING", age=10.0,
                                 arg_bytes=big, now=now)
    flight_recorder.clear()
    wd._check_stragglers(now)
    assert "victim" in wd._flagged_tasks
    assert "bigok" not in wd._flagged_tasks
    ev = [e for e in flight_recorder.dump()
          if e.get("event") == "straggler"]
    assert len(ev) == 1
    assert ev[0]["arg_bytes"] == small
    assert ev[0]["size_bucket"] == wd._size_bucket(small)
    assert ev[0]["pooled_baseline"] is False

    # Unseen size class (medium) -> pooled fallback, flagged only past
    # the pooled threshold, and marked as a pooled verdict.
    srv.tasks["pooledhit"] = _mk_rec("work", "RUNNING", age=100.0,
                                     arg_bytes=1 << 16, now=now)
    wd._check_stragglers(now)
    assert "pooledhit" in wd._flagged_tasks
    ev = [e for e in flight_recorder.dump()
          if e.get("event") == "straggler" and e["task"] == "pooledhit"]
    assert ev[0]["pooled_baseline"] is True
    flight_recorder.clear()


# ---------------------------------------------------------------------------
# Profile history rings (in-process cluster)
# ---------------------------------------------------------------------------

def test_profile_history_rings_and_percentiles(monkeypatch):
    import ray_tpu

    monkeypatch.setenv("RAY_TPU_PROFILE_HISTORY", "16")
    monkeypatch.setenv("RAY_TPU_PROFILE_SAMPLE_INTERVAL_S", "0.1")
    rt = ray_tpu.init(num_cpus=2)
    try:
        # Workers spawn on demand; run a task so at least one reporter
        # exists, then retune its sampler over the wire.
        @ray_tpu.remote
        def noop():
            return 1

        assert ray_tpu.get(noop.remote(), timeout=60) == 1
        rt.core.client.call({"op": "set_profile_config",
                             "enabled": True, "interval_s": 0.1})
        deadline = time.time() + 30
        prof = {}
        while time.time() < deadline:
            prof = rt.core.client.call({"op": "get_profile",
                                        "samples": True})
            hist = prof.get("history", {})
            if hist and all(h["samples"] >= 3 for h in hist.values()):
                break
            time.sleep(0.2)
        assert prof["history_capacity"] == 16
        assert prof["history"], prof
        for wh, h in prof["history"].items():
            assert 3 <= h["samples"] <= 16
            assert h["last_ts"] >= h["first_ts"] > 0
            assert "cpu_percent" in h["percentiles"]
            p = h["percentiles"]["cpu_percent"]
            assert p["p50"] <= p["p95"]
            # samples=True attaches the bounded raw ring.
            assert len(h["raw"]) == h["samples"]
        # The watchdog consumes the same distributions.
        wd = prof["watchdog"]
        assert wd["profile_distributions"].keys() == \
            prof["history"].keys()
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Head restart: pre-kill history survives kill -9 (acceptance)
# ---------------------------------------------------------------------------

def _start_head(port, tmp_path, env_extra):
    env = dict(os.environ)
    env["RAY_TPU_CONTROL_PORT"] = str(port)
    env["RAY_TPU_GCS_STORE_PATH"] = str(tmp_path / "gcs.journal")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(env_extra)
    return subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "start", "--head",
         "--num-cpus", "2", "--no-dashboard", "--block"],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _wait_head(port, timeout=60):
    from ray_tpu.core import rpc

    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            c = rpc.Client(f"127.0.0.1:{port}", connect_timeout=1.0)
            c.call({"op": "ping"}, timeout=3.0)
            return c
        except Exception:
            time.sleep(0.3)
    raise AssertionError(f"head on port {port} never came up")


def test_head_restart_serves_prekill_spans_and_flight(tmp_path):
    """kill -9 the head mid-run; the restarted head answers
    `harvest_spans` (poll=False) and `flight_recorder` with the
    pre-kill history, rehydrated from the ops journal."""
    import ray_tpu
    from ray_tpu.util import tracing

    ops_dir = str(tmp_path / "ops")
    env_extra = {"RAY_TPU_OPS_JOURNAL_DIR": ops_dir,
                 "RAY_TPU_OPS_JOURNAL_FSYNC_S": "0.05"}
    head = _start_head(PORT, tmp_path, env_extra)
    c = None
    try:
        c = _wait_head(PORT)
        c.close()
        c = None
        rt = ray_tpu.init(address=f"127.0.0.1:{PORT}")
        try:
            # This process's span ring outlives tests: a span an earlier
            # test left there is harvested too, and one that ended over
            # 120 s ago falls outside the windowed query below.
            tracing.clear_spans()
            tracing.enable_tracing()

            @ray_tpu.remote
            def work(x):
                return x + 1

            with tracing.trace_span("prekill-root"):
                assert ray_tpu.get([work.remote(i) for i in range(4)],
                                   timeout=60) == [1, 2, 3, 4]
            # Harvest pushes the worker spans into the head's store,
            # which spills them to the journal.
            reply = rt.core.client.call(
                {"op": "harvest_spans", "timeout_s": 15.0})
            prekill_ids = {s["span_id"] for s in reply["spans"]}
            assert prekill_ids
        finally:
            tracing.disable_tracing()
            tracing.clear_spans()
            ray_tpu.shutdown()
        # Spans + head-side flight events must be fsynced before the
        # kill; poll the journal files instead of guessing a sleep.
        deadline = time.time() + 20
        while time.time() < deadline:
            ids_on_disk = {e["d"][0] for e in
                           journal.replay(ops_dir, "spans")}
            if prekill_ids <= ids_on_disk and \
                    journal.replay(ops_dir, "flight"):
                break
            time.sleep(0.2)
        assert prekill_ids <= ids_on_disk
        t_kill = time.time()

        head.kill()  # SIGKILL: no flush, no atexit
        head.wait(timeout=15)
        head = _start_head(PORT, tmp_path, env_extra)
        c = _wait_head(PORT)

        reply = c.call({"op": "harvest_spans", "poll": False,
                        "timeout_s": 10.0}, timeout=30.0)
        assert reply["workers_polled"] == 0
        served = {s["span_id"] for s in reply["spans"]}
        assert prekill_ids <= served, (
            f"restarted head lost {len(prekill_ids - served)} "
            f"pre-kill spans")
        # Time-windowed query: everything served ended before the kill.
        reply = c.call({"op": "harvest_spans", "poll": False,
                        "since": t_kill - 120.0, "timeout_s": 10.0},
                       timeout=30.0)
        assert {s["span_id"] for s in reply["spans"]} >= prekill_ids
        fl = c.call({"op": "flight_recorder", "since": t_kill - 120.0},
                    timeout=30.0)
        pre = [e for e in fl["events"] if e["ts"] < t_kill]
        assert pre, "restarted head serves no pre-kill flight events"
    finally:
        if c is not None:
            c.close()
        head.kill()
        try:
            head.wait(timeout=10)
        # raylint: allow-swallow(teardown reap; a stuck zombie must not mask the test result)
        except subprocess.TimeoutExpired:
            pass


# ---------------------------------------------------------------------------
# opsdump exporter
# ---------------------------------------------------------------------------

def test_opsdump_exports_chrome_trace(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import opsdump
    finally:
        sys.path.pop(0)
    d = str(tmp_path)
    js = journal.Journal(d, "spans", fsync_s=0.01)
    jf = journal.Journal(d, "flight", fsync_s=0.01)
    jm = journal.Journal(d, "metrics", fsync_s=0.01)
    try:
        t0 = time.time()
        js.append(["s1", "", "tr1", "step", t0, t0 + 0.5, None,
                   "w" * 8, 4242])
        jf.append({"ts": t0, "category": "health", "event": "straggler",
                   "task": "t1"})
        jm.append({"snapshots": [{"name": "m_total",
                                  "series": [[[["k", "a"]], 3.0]]}]})
        for j in (js, jf, jm):
            assert j.flush(timeout=10)
    finally:
        for j in (js, jf, jm):
            j.close()
    events = opsdump.build_trace(d)
    phases = {e["ph"] for e in events}
    assert "X" in phases and "i" in phases and "C" in phases
    slice_ev = next(e for e in events if e.get("ph") == "X")
    assert slice_ev["name"] == "step" and slice_ev["pid"] == 4242
    marker = next(e for e in events if e.get("ph") == "i")
    assert marker["name"] == "straggler"
    counter = next(e for e in events if e.get("ph") == "C")
    assert counter["args"]["value"] == 3.0
    # CLI: --stats and a trace file.
    out = str(tmp_path / "trace.json")
    assert opsdump.main(["--dir", d, "--out", out]) == 0
    with open(out) as f:
        doc = json.load(f)
    assert doc["traceEvents"]
    assert opsdump.main(["--dir", d, "--stats"]) == 0
