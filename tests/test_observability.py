"""Observability stack tests: metrics, timeline, tracing, log monitor,
usage stats (SURVEY.md §5 aux subsystems / §2.2 P15–P21)."""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import tracing
from ray_tpu.util.metrics import (
    Counter,
    Gauge,
    Histogram,
    snapshots_to_prometheus_text,
)


# ---------------------------------------------------------------------------
# Metrics: local registry + exposition
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_exposition():
    c = Counter("test_requests_total", "reqs", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    g = Gauge("test_temperature", "deg")
    g.set(42.5)
    h = Histogram("test_latency", "s", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = snapshots_to_prometheus_text(
        [c.snapshot(), g.snapshot(), h.snapshot()])
    assert 'test_requests_total{route="/a"} 3.0' in text
    assert 'test_requests_total{route="/b"} 1.0' in text
    assert "test_temperature 42.5" in text
    assert 'test_latency_bucket{le="0.1"} 1' in text
    assert 'test_latency_bucket{le="1.0"} 2' in text
    assert 'test_latency_bucket{le="+Inf"} 3' in text
    assert "test_latency_count 3" in text
    assert "# TYPE test_requests_total counter" in text


def test_metric_tag_validation():
    c = Counter("test_tags_strict", tag_keys=("k",))
    with pytest.raises(ValueError):
        c.inc(tags={"other": "x"})
    with pytest.raises(ValueError):
        c.inc(-1.0)
    c.set_default_tags({"k": "v"})
    c.inc()
    assert c.snapshot()["series"][(("k", "v"),)] == 1.0


@pytest.mark.usefixtures("ray_start_regular")
def test_metrics_aggregate_across_workers():
    """User metrics recorded inside worker processes surface in the
    driver-side aggregation (KV publish path)."""

    @ray_tpu.remote
    def record():
        from ray_tpu.util.metrics import Counter, publish_now

        c = Counter("test_worker_events", tag_keys=())
        c.inc(5.0)
        assert publish_now()
        return True

    assert ray_tpu.get(record.remote())
    from ray_tpu.core.runtime import get_runtime
    rt = get_runtime()
    text = metrics_mod.aggregate_prometheus_text(rt)
    assert "test_worker_events 5.0" in text
    # Built-in state gauges ride along.
    assert "ray_tpu_tasks" in text
    assert "ray_tpu_nodes" in text


# ---------------------------------------------------------------------------
# Timeline
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("ray_start_regular")
def test_timeline_chrome_trace(tmp_path):
    @ray_tpu.remote
    def work(x):
        time.sleep(0.05)
        return x

    ray_tpu.get([work.remote(i) for i in range(3)])
    from ray_tpu.util.timeline import timeline

    path = str(tmp_path / "trace.json")
    # The task_done control message can land just after get() returns;
    # poll briefly until all three records carry finish timestamps.
    deadline = time.time() + 5
    while True:
        events = timeline(path)
        done = [e for e in events
                if e.get("ph") == "X" and e["cat"] == "task"]
        if len(done) >= 3 or time.time() > deadline:
            break
        time.sleep(0.05)
    with open(path) as f:
        assert json.load(f) == events
    slices = [e for e in events if e.get("ph") == "X" and e["cat"] == "task"]
    assert len(slices) >= 3
    for e in slices:
        assert e["dur"] >= 0.05 * 1e6 * 0.5  # at least ~the sleep
        assert e["args"]["task_id"]
    assert any(e.get("ph") == "M" for e in events)  # row labels


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("ray_start_regular")
def test_tracing_spans_and_submit_instrumentation(tmp_path):
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        @ray_tpu.remote
        def traced_task():
            return 1

        with tracing.trace_span("outer", {"step": "1"}):
            with tracing.trace_span("inner"):
                ref = traced_task.remote()
        ray_tpu.get(ref)
        spans = tracing.get_spans()
        names = [s["name"] for s in spans]
        assert "outer" in names and "inner" in names
        assert any(n.startswith("submit:") for n in names)
        # Nesting: inner's parent is outer; submit's parent is inner.
        by_name = {s["name"]: s for s in spans}
        assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
        submit = next(s for s in spans if s["name"].startswith("submit:"))
        assert submit["parent_id"] == by_name["inner"]["span_id"]
        # Chrome export merges spans + cluster task slices.
        path = str(tmp_path / "spans.json")
        n = tracing.export_chrome_trace(path)
        assert n >= len(spans)
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()


def test_tracing_disabled_is_noop():
    tracing.clear_spans()
    with tracing.trace_span("nothing"):
        pass
    assert tracing.get_spans() == []


# ---------------------------------------------------------------------------
# Log monitor
# ---------------------------------------------------------------------------

def test_log_monitor_streams_worker_output(tmp_path, capsys):
    import io

    from ray_tpu.core.log_monitor import LogMonitor

    logs = tmp_path / "logs"
    logs.mkdir()
    out = io.StringIO()
    mon = LogMonitor(str(tmp_path), out=out, err=out).start()
    try:
        with open(logs / "worker-abcdef012345.out", "w") as f:
            f.write("hello from worker\n")
        deadline = time.time() + 5
        while "hello from worker" not in out.getvalue():
            assert time.time() < deadline, out.getvalue()
            time.sleep(0.05)
        assert "(abcdef01)" in out.getvalue()
    finally:
        mon.stop()


@pytest.mark.usefixtures("ray_start_regular")
def test_worker_prints_reach_driver():
    """End to end: a task's print() lands in the worker's session log file
    and a monitor attached to the live session streams it. (The built-in
    monitor prints to the real stdout, which pytest's capture layers hide
    from fixtures — so attach a second monitor with an explicit sink.)"""
    import io

    from ray_tpu.core.log_monitor import LogMonitor
    from ray_tpu.core.runtime import get_runtime

    out = io.StringIO()
    mon = LogMonitor(get_runtime().session_dir, out=out, err=out).start()
    try:
        @ray_tpu.remote
        def chatty():
            print("WORKER_SAYS_HI")
            return 0

        ray_tpu.get(chatty.remote())
        deadline = time.time() + 5
        while "WORKER_SAYS_HI" not in out.getvalue():
            assert time.time() < deadline, out.getvalue()
            time.sleep(0.1)
    finally:
        mon.stop()


# ---------------------------------------------------------------------------
# Usage stats
# ---------------------------------------------------------------------------

def test_usage_stats_report(tmp_path):
    from ray_tpu.util import usage_stats

    usage_stats.record_library_usage("testlib")
    usage_stats.record_extra_usage_tag("mesh_axes", "data,fsdp")
    path = usage_stats.write_usage_report(str(tmp_path))
    with open(path) as f:
        report = json.load(f)
    assert report["counters"].get("library:testlib", 0) >= 1
    assert report["tags"]["mesh_axes"] == "data,fsdp"


# ---------------------------------------------------------------------------
# Dashboard endpoints
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("ray_start_regular")
def test_dashboard_metrics_and_timeline_endpoints():
    import urllib.request

    from ray_tpu.dashboard import Dashboard

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get(f.remote())
    from ray_tpu.core.runtime import get_runtime
    rt = get_runtime()
    dash = Dashboard(rt)
    try:
        text = urllib.request.urlopen(dash.url + "/metrics").read().decode()
        assert "ray_tpu_tasks" in text
        tl = json.loads(
            urllib.request.urlopen(dash.url + "/api/timeline").read())
        assert isinstance(tl, list) and len(tl) >= 1
    finally:
        dash.stop()


# ---------------------------------------------------------------------------
# On-demand worker profiling (reference: dashboard reporter
# profile_manager.py py-spy/memray; SURVEY §5 TPU-native jax.profiler add)
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("ray_start_regular")
def test_stack_profile_of_busy_worker():
    import time as _time

    import ray_tpu
    from ray_tpu.state.api import list_workers, profile_worker

    @ray_tpu.remote
    def snooze_for_profiler():
        _time.sleep(4.0)
        return 1

    ref = snooze_for_profiler.remote()
    # Wait until a pool worker is busy with it.
    deadline = _time.time() + 15
    busy = None
    while busy is None and _time.time() < deadline:
        # "leased" = executing via the owner-direct lease path
        busy = next((w for w in list_workers()
                     if w["kind"] == "pool"
                     and w["state"] in ("busy", "leased")),
                    None)
        _time.sleep(0.05)
    assert busy is not None
    dump = profile_worker(busy["worker_id"], kind="stack")
    assert "snooze_for_profiler" in dump, dump[:2000]
    assert "Thread" in dump
    assert ray_tpu.get(ref) == 1


@pytest.mark.usefixtures("ray_start_regular")
def test_jax_trace_profile_of_driver():
    """jax_trace writes an xplane trace dir; profiling the driver keeps
    the test hermetic (jax is already imported here)."""
    import os as _os

    import ray_tpu
    from ray_tpu.state.api import profile_worker

    rt = ray_tpu.init()
    out_dir = profile_worker(rt.core.worker_hex, kind="jax_trace",
                             duration_s=0.3)
    assert _os.path.isdir(out_dir), out_dir
    # The profiler wrote something (plugins/profile/... xplane files).
    found = [f for _, _, fs in _os.walk(out_dir) for f in fs]
    assert found, f"empty trace dir {out_dir}"


@pytest.mark.usefixtures("ray_start_regular")
def test_profile_unknown_worker_errors():
    import pytest as _pytest

    from ray_tpu.state.api import profile_worker

    with _pytest.raises(Exception, match="no live worker"):
        profile_worker("ff" * 14)


# ---------------------------------------------------------------------------
# Cross-process trace propagation (tracing.py trace_ctx riding TaskSpecs)
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("ray_start_regular")
def test_trace_propagation_driver_worker_nested():
    """A driver→worker→nested-task chain yields task records sharing ONE
    trace_id with parent links pointing back through the chain to the
    driver's submit span — no extra wire round-trips involved."""
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        @ray_tpu.remote
        def leaf_task():
            return 1

        @ray_tpu.remote
        def branch_task():
            return ray_tpu.get(leaf_task.remote())

        with tracing.trace_span("root"):
            ref = branch_task.remote()
        assert ray_tpu.get(ref) == 1

        from ray_tpu.state.api import list_tasks
        by = {}
        deadline = time.time() + 10
        while time.time() < deadline:
            by = {}
            for r in list_tasks():
                nm = r.get("name") or ""
                if r.get("trace_id") and r.get("span_id"):
                    if "branch_task" in nm:
                        by["branch"] = r
                    elif "leaf_task" in nm:
                        by["leaf"] = r
            if len(by) == 2 and by["leaf"].get("parent_span_id"):
                break
            time.sleep(0.05)
        assert len(by) == 2, f"records missing trace fields: {by}"
        branch, leaf = by["branch"], by["leaf"]
        # One trace across all three processes.
        assert branch["trace_id"] == leaf["trace_id"]
        # Nested task's parent is the branch task's execution span.
        assert leaf["parent_span_id"] == branch["span_id"]
        # Branch task's parent is the driver's submit span.
        submit = next(s for s in tracing.get_spans()
                      if s["name"].startswith("submit:")
                      and "branch_task" in s["name"])
        assert branch["parent_span_id"] == submit["span_id"]
        assert submit["trace_id"] == branch["trace_id"]
        # The submit span nests under the user's root span.
        root = next(s for s in tracing.get_spans() if s["name"] == "root")
        assert submit["parent_id"] == root["span_id"]
        # get_task surfaces the same record by id.
        from ray_tpu.state.api import get_task
        rec = get_task(branch["task_id"])
        assert rec is not None and rec["trace_id"] == branch["trace_id"]
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()


def test_span_ring_bounded(monkeypatch):
    """Long-running drivers must not leak spans: the ring caps at
    RAY_TPU_TRACE_MAX_SPANS and counts evictions."""
    monkeypatch.setenv("RAY_TPU_TRACE_MAX_SPANS", "16")
    tracing.clear_spans()
    tracing.enable_tracing()  # re-reads the cap
    try:
        for i in range(40):
            tracing.record_span(f"s{i}", 0.0, 0.0)
        spans = tracing.get_spans()
        assert len(spans) == 16
        assert tracing.dropped_span_count() == 24
        # Oldest evicted, newest kept.
        assert spans[-1]["name"] == "s39"
        assert spans[0]["name"] == "s24"
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
        monkeypatch.delenv("RAY_TPU_TRACE_MAX_SPANS")
        tracing.enable_tracing()
        tracing.disable_tracing()


# ---------------------------------------------------------------------------
# Wire-level metrics (rpc.py WIRE → metrics exposition)
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("ray_start_regular")
def test_wire_metrics_exported_after_burst():
    """After a task burst, /metrics-style aggregation exposes nonzero
    rpc frame/batch counters straight from the rpc layer."""

    @ray_tpu.remote
    def noop(i):
        return i

    assert ray_tpu.get([noop.remote(i) for i in range(100)]) == \
        list(range(100))
    from ray_tpu.core.runtime import get_runtime
    rt = get_runtime()
    text = metrics_mod.aggregate_prometheus_text(rt)
    assert 'rpc_frames_total{direction="sent"}' in text
    assert "rpc_batch_size_count" in text
    sent = float(next(
        line.split()[-1] for line in text.splitlines()
        if line.startswith('rpc_frames_total{direction="sent"}')))
    assert sent > 0
    recv = float(next(
        line.split()[-1] for line in text.splitlines()
        if line.startswith('rpc_frames_total{direction="received"}')))
    assert recv > 0
    assert "rpc_frames_by_kind_total" in text


def test_wire_snapshot_shapes():
    from ray_tpu.core import rpc

    snaps = rpc.wire_metric_snapshots()
    names = {s["name"] for s in snaps}
    assert {"rpc_frames_total", "rpc_msgs_total", "rpc_batches_total",
            "rpc_bytes_total", "rpc_batch_size"} <= names
    hist = next(s for s in snaps if s["name"] == "rpc_batch_size")
    assert hist["kind"] == "histogram"
    assert len(hist["boundaries"]) + 1 == len(hist["series"][()][0])
    # Renders cleanly through the standard exposition path.
    text = snapshots_to_prometheus_text(snaps)
    assert "# TYPE rpc_batch_size histogram" in text


# ---------------------------------------------------------------------------
# Batched task-event streaming (worker → head delta vectors)
# ---------------------------------------------------------------------------

def test_head_frames_merges_task_event_runs():
    """Unit: a run of queued task_event deltas collapses into ONE
    task_events frame, with same-task deltas merged (later keys overlay,
    earlier keys like the arrival timestamp survive)."""
    from ray_tpu.core.runtime import CoreClient

    items = [
        ("task_event", {"task_id": "aa", "state": "RECEIVED",
                        "received": 1.0}),
        ("task_event", {"task_id": "bb", "state": "RECEIVED",
                        "received": 2.0}),
        ("task_event", {"task_id": "aa", "state": "RUNNING",
                        "start": 1.5}),
        ("task_event", {"task_id": "aa", "state": "FINISHED",
                        "start": 1.5, "end": 1.9}),
    ]
    frames = [msg for _, msg in CoreClient._head_frames(items)]
    assert len(frames) == 1
    assert frames[0]["op"] == "task_events"
    events = {e["task_id"]: e for e in frames[0]["events"]}
    assert len(events) == 2
    # Merged delta keeps the arrival time AND the final state.
    assert events["aa"]["state"] == "FINISHED"
    assert events["aa"]["received"] == 1.0
    assert events["aa"]["end"] == 1.9
    # First-seen order preserved.
    assert [e["task_id"] for e in frames[0]["events"]] == ["aa", "bb"]


@pytest.mark.usefixtures("ray_start_regular")
def test_task_event_delta_batching_under_burst():
    """A burst of N lease-path tasks reaches the head in far fewer
    task_events frames than tasks (the events ride the coalescing
    flusher as delta vectors) — the streaming analogue of
    test_rpc_batching's refcount-netting assertion."""
    from ray_tpu.core.runtime import get_runtime
    rt = get_runtime()
    ctl = getattr(rt, "control", None)
    if ctl is None or ctl._m_task_events is None:
        pytest.skip("needs an in-process head with metrics")

    def total(counter):
        return sum(counter.snapshot()["series"].values() or [0.0])

    ev0, fr0 = total(ctl._m_task_events), total(ctl._m_task_event_frames)

    @ray_tpu.remote
    def tick(i):
        return i

    n = 300
    assert ray_tpu.get([tick.remote(i) for i in range(n)]) == list(range(n))
    deadline = time.time() + 10
    while time.time() < deadline:
        events = total(ctl._m_task_events) - ev0
        frames = total(ctl._m_task_event_frames) - fr0
        # Every task produces a terminal event (merged deltas count 1).
        if events >= n:
            break
        time.sleep(0.05)
    assert events >= n, f"only {events} events ingested"
    assert frames < events, (frames, events)
    assert frames < n, f"{frames} frames for {n} tasks — no batching"
    # The streamed records actually landed: finished lease-path tasks
    # are visible to the state API with their timing fields.
    from ray_tpu.state.api import list_tasks
    done = [r for r in list_tasks()
            if "tick" in (r.get("name") or "")
            and r["state"] == "FINISHED"]
    assert len(done) >= n * 0.9
    assert any(r.get("received_at") for r in done)


# ---------------------------------------------------------------------------
# Flight recorder (bounded wire/scheduler event ring)
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_caps():
    """Bounded ring: capacity honored, evictions counted.  Background
    cluster threads from sibling tests may record concurrently, so
    assertions filter on a private category and use lower bounds."""
    from ray_tpu.util import flight_recorder as fr

    fr.configure(capacity=16, enable=True)
    try:
        for i in range(40):
            fr.record("_test_ring", "e", i=i)
        st = fr.stats()
        assert st["capacity"] == 16
        assert st["events"] == 16
        assert st["dropped"] >= 24
        mine = [e for e in fr.dump() if e["category"] == "_test_ring"]
        assert mine[-1]["i"] == 39  # newest kept
        assert all(e["i"] >= 24 for e in mine)  # the oldest 24 evicted
        assert fr.dump(last=4) == fr.dump()[-4:]
    finally:
        fr.configure(capacity=0, enable=True)  # back to env default


def test_flight_recorder_captures_wire_batches():
    """A coalesced drain round drops a wire/batch_flush event in the
    ring (deterministic via the gated stub sock — the same contention
    setup as test_rpc_batching's sender test)."""
    import pickle as _pickle
    import threading as _threading

    from ray_tpu.core import rpc
    from ray_tpu.util import flight_recorder as fr

    class _GatedSock:
        def __init__(self):
            self.gate = _threading.Event()
            self.sent = _threading.Event()

        def sendall(self, data):
            self.sent.set()
            self.gate.wait()

    fr.clear()
    sock = _GatedSock()
    sender = rpc._CoalescingSender(sock, _threading.Lock())
    t = _threading.Thread(
        target=sender.send,
        args=(rpc.KIND_ONEWAY, 0, _pickle.dumps({"i": 0})))
    t.start()
    assert sock.sent.wait(2.0)
    for i in range(1, 6):
        sender.send(rpc.KIND_ONEWAY, 0, _pickle.dumps({"i": i}))
    sock.gate.set()
    t.join(2.0)
    sender.flush()
    flushes = [e for e in fr.dump()
               if e["category"] == "wire" and e["event"] == "batch_flush"]
    assert any(e["msgs"] == 5 for e in flushes), flushes
    # Timeline surfaces the ring as a dedicated wire lane.
    from ray_tpu.util import timeline as tl
    lanes = {e["pid"] for e in tl.flight_recorder_events()
             if e.get("ph") == "i"}
    assert tl.WIRE_PID in lanes


@pytest.mark.usefixtures("ray_start_regular")
def test_flight_recorder_captures_scheduler_decisions():
    from ray_tpu.util import flight_recorder as fr

    @ray_tpu.remote
    def spark(i):
        return i

    ray_tpu.get([spark.remote(i) for i in range(50)])
    deadline = time.time() + 5
    grants = []
    while time.time() < deadline:
        grants = [e for e in fr.dump()
                  if e["category"] == "scheduler"
                  and e["event"] == "lease_grant"]
        if grants:
            break
        time.sleep(0.05)
    assert grants, "no lease_grant events recorded"
    assert any(e.get("granted", 0) >= 1 for e in grants)
    from ray_tpu.util import timeline as tl
    lanes = {e["pid"] for e in tl.flight_recorder_events()
             if e.get("ph") == "i"}
    assert tl.SCHED_PID in lanes


# ---------------------------------------------------------------------------
# Metrics snapshot freshness (stale-key expiry + clean unpublish)
# ---------------------------------------------------------------------------

def test_aggregate_skips_and_deletes_stale_snapshots():
    import pickle as _pickle

    store = {
        "__metrics__/old": _pickle.dumps({
            "ts": time.time() - 3600,
            "snapshots": [{"name": "zombie_metric", "kind": "counter",
                           "description": "", "series": {(): 1.0}}]}),
        "__metrics__/fresh": _pickle.dumps({
            "ts": time.time(),
            "snapshots": [{"name": "live_metric", "kind": "counter",
                           "description": "", "series": {(): 2.0}}]}),
    }

    def kv_call(msg):
        if msg["op"] == "kv_keys":
            return [k for k in store if k.startswith(msg["prefix"])]
        if msg["op"] == "kv_get":
            return store.get(msg["key"])
        if msg["op"] == "kv_del":
            store.pop(msg["key"], None)
            return True
        raise AssertionError(msg)

    snaps = metrics_mod.aggregate_snapshots(kv_call)
    names = {s["name"] for s in snaps}
    assert "live_metric" in names
    assert "zombie_metric" not in names
    # The stale key was garbage-collected, not just skipped.
    assert "__metrics__/old" not in store
    # skip_ident excludes the caller's own key (it reads itself live).
    assert metrics_mod.aggregate_snapshots(kv_call,
                                           skip_ident="fresh") == []


def test_metrics_ttl_env_knob(monkeypatch):
    monkeypatch.setenv("RAY_TPU_METRICS_TTL_S", "0.05")
    import pickle as _pickle

    store = {"__metrics__/w": _pickle.dumps({
        "ts": time.time() - 1.0,
        "snapshots": [{"name": "m", "kind": "counter",
                       "description": "", "series": {(): 1.0}}]})}

    def kv_call(msg):
        if msg["op"] == "kv_keys":
            return list(store)
        if msg["op"] == "kv_get":
            return store.get(msg["key"])
        if msg["op"] == "kv_del":
            store.pop(msg["key"], None)
            return True

    assert metrics_mod.aggregate_snapshots(kv_call) == []
    assert not store  # expired under the tightened TTL


def test_unpublish_deletes_kv_key(monkeypatch):
    deleted = []

    def kv_call(msg):
        assert msg["op"] == "kv_del"
        deleted.append(msg["key"])
        return True

    # Never published in this state: unpublish is a no-op.
    monkeypatch.setattr(metrics_mod, "_published", False)
    metrics_mod.unpublish(kv_call, "abc")
    assert deleted == []
    monkeypatch.setattr(metrics_mod, "_published", True)
    metrics_mod.unpublish(kv_call, "abc")
    assert deleted == ["__metrics__/abc"]
    assert metrics_mod._published is False


# ---------------------------------------------------------------------------
# Timeline sampling + lane ordering
# ---------------------------------------------------------------------------

def test_timeline_sampling_keeps_first_and_last():
    from ray_tpu.util.timeline import _sample_uniform

    tasks = [{"i": i} for i in range(1000)]
    for cap in (2, 3, 7, 100, 999):
        picked = _sample_uniform(tasks, cap)
        assert len(picked) <= cap
        assert picked[0]["i"] == 0, cap
        assert picked[-1]["i"] == 999, cap
    assert _sample_uniform(tasks, 1) == [tasks[0]]


def test_timeline_lane_sort_indices():
    """The driver scheduling row is pinned first (sort_index -1) and
    trace ids ride the task slices' args."""
    from ray_tpu.util.timeline import DRIVER_PID, timeline_events

    class FakeRuntime:
        @staticmethod
        def state_list(kind):
            assert kind == "tasks"
            return [{"task_id": "t1", "name": "job", "state": "FINISHED",
                     "worker": "w", "pid": 4242, "submitted_at": 1.0,
                     "started_at": 2.0, "finished_at": 3.0,
                     "trace_id": "tr", "span_id": "sp",
                     "parent_span_id": "pa"}]

    events = timeline_events(FakeRuntime(), include_flight=False)
    sort_meta = {e["pid"]: e["args"]["sort_index"] for e in events
                 if e.get("ph") == "M"
                 and e.get("name") == "process_sort_index"}
    assert sort_meta[DRIVER_PID] == -1
    task = next(e for e in events
                if e.get("ph") == "X" and e["cat"] == "task")
    assert task["args"]["trace_id"] == "tr"
    assert task["args"]["parent_span_id"] == "pa"
    sched = next(e for e in events
                 if e.get("ph") == "X" and e["cat"] == "scheduling")
    assert sched["pid"] == DRIVER_PID


# ---------------------------------------------------------------------------
# Dashboard: /api/trace + /api/flight_recorder
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("ray_start_regular")
def test_dashboard_trace_and_flight_recorder_endpoints():
    import urllib.request

    from ray_tpu.dashboard import Dashboard

    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        @ray_tpu.remote
        def f():
            return 1

        ray_tpu.get(f.remote())
        from ray_tpu.core.runtime import get_runtime
        rt = get_runtime()
        dash = Dashboard(rt)
        try:
            tr = json.loads(urllib.request.urlopen(
                dash.url + "/api/trace").read())
            assert isinstance(tr, list) and tr
            cats = {e.get("cat") for e in tr}
            assert "span" in cats  # driver spans lane present
            fr = json.loads(urllib.request.urlopen(
                dash.url + "/api/flight_recorder").read())
            assert "events" in fr and "stats" in fr
            assert fr["stats"]["capacity"] >= 16
            # Wire counters surfaced on the Prometheus endpoint too.
            text = urllib.request.urlopen(
                dash.url + "/metrics").read().decode()
            assert "rpc_frames_total" in text
        finally:
            dash.stop()
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()


def test_logging_config_structured_workers():
    """ray_tpu.LoggingConfig (counterpart of ray.LoggingConfig,
    _private/ray_logging/): JSON encoding + level apply to the driver
    and propagate to workers via the session environment."""
    import json
    import logging

    import ray_tpu
    from ray_tpu.core.logging_config import JsonFormatter, LoggingConfig

    # Formatter unit: record -> one JSON object with context fields.
    fmt = JsonFormatter(extra_attrs=("lineno",))
    rec = logging.LogRecord("my.logger", logging.WARNING, __file__, 42,
                            "boom %s", ("x",), None)
    obj = json.loads(fmt.format(rec))
    assert obj["levelname"] == "WARNING"
    assert obj["name"] == "my.logger"
    assert obj["message"] == "boom x"
    assert obj["lineno"] == 42

    with pytest.raises(ValueError):
        LoggingConfig(encoding="YAML")

    root = logging.getLogger()
    prev_level = root.level
    prev_formatters = [(h, h.formatter) for h in root.handlers]
    ray_tpu.init(num_cpus=2, log_to_driver=False,
                 logging_config=LoggingConfig(encoding="JSON",
                                              log_level="DEBUG"))
    try:
        assert logging.getLogger().level == logging.DEBUG

        @ray_tpu.remote
        def probe():
            import json as _json
            import logging as _logging
            import os as _os

            root = _logging.getLogger()
            h = root.handlers[0]
            rec = _logging.LogRecord("w", _logging.INFO, "f", 1,
                                     "from-worker", (), None)
            return {
                "level": root.level,
                "formatted": h.formatter.format(rec),
                "env": _os.environ.get("RAY_TPU_LOGGING_CONFIG", ""),
            }

        out = ray_tpu.get(probe.remote(), timeout=120)
        assert out["level"] == logging.DEBUG
        parsed = json.loads(out["formatted"])
        assert parsed["message"] == "from-worker"
        assert parsed.get("worker_id")  # executing-context join key
        assert "JSON" in out["env"]
    finally:
        ray_tpu.shutdown()
        import os

        assert "RAY_TPU_LOGGING_CONFIG" not in os.environ
        root.setLevel(prev_level)  # don't leak DEBUG into later tests
        for h, f in prev_formatters:
            h.setFormatter(f)
