"""Request-journey tracing + per-deployment SLO attribution.

Engine-level: queue-wait histogram on EVERY outcome (admit and shed),
phase spans (queue/prefill/decode) parented under the replica span,
per-request SLO samples, and the sampled per-step engine snapshot.
Controller-level: load-report fold into sliding-window percentiles
(/api/serve_slo).  Plus the opsdump per-request lanes, the committed
tracing-overhead budget, and one end-to-end cluster test: an HTTP
request through a disaggregated gateway renders as ONE parent-linked
trace spanning proxy + both replica pools, /api/serve_slo reports
non-trivial percentiles, and a SIGKILL mid-request leaves a partial
phase timeline in the durable ops journal.
"""

import json
import os
import signal
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import tracing

_PS = 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine(**over):
    kw = dict(page_size=_PS, num_pages=64, max_batch=4,
              queue_timeout_s=0)
    kw.update(over)
    return LLMEngine(tfm.TransformerConfig.tiny(), **kw)


def _drain(eng):
    done = {}
    while eng.has_work():
        done.update(eng.step())
    return done


def _server(**over):
    from ray_tpu.serve import llm as llm_mod

    kw = dict(page_size=_PS, num_pages=64, max_batch=4)
    kw.update(over)
    return llm_mod.LLMServer.func_or_class(**kw)


def _hist_counts(name):
    """{tags_key: observation_count} for one histogram, from the
    process-local metric registry (cumulative across tests)."""
    from ray_tpu.util.metrics import local_snapshots

    for s in local_snapshots():
        if s["name"] == name:
            return {k: v[2] for k, v in s["series"].items()}
    return {}


_ADMITTED = (("outcome", "admitted"),)
_SHED = (("outcome", "shed"),)


# ---------------------------------------------------------------------------
# Engine: queue-wait on every outcome, phase spans, SLO samples
# ---------------------------------------------------------------------------


def test_queue_wait_observed_on_admit_and_shed():
    """ray_tpu_serve_queue_wait_seconds fires on BOTH outcomes: once
    with outcome=admitted when a request seats, once with outcome=shed
    when the deadline retires it from the queue — so the histogram's
    total count equals requests that LEFT the queue, not a biased
    admitted-only view."""
    before = _hist_counts("ray_tpu_serve_queue_wait_seconds")
    eng = _engine()
    eng.add_request([1, 2, 3, 4, 5], 4)
    _drain(eng)
    mid = _hist_counts("ray_tpu_serve_queue_wait_seconds")
    assert mid.get(_ADMITTED, 0) == before.get(_ADMITTED, 0) + 1

    eng.add_request([6, 7, 8], 4, deadline_s=0.001)
    time.sleep(0.05)
    eng.step()  # _shed_expired retires the expired request
    after = _hist_counts("ray_tpu_serve_queue_wait_seconds")
    assert after.get(_SHED, 0) == mid.get(_SHED, 0) + 1
    assert after.get(_ADMITTED, 0) == mid.get(_ADMITTED, 0)
    # The shed also lands in the SLO sample ring (attributed, not just
    # counted) with its queue wait.
    shed = [s for s in eng.slo_samples if "shed" in s]
    assert shed and shed[-1]["queue_wait"] > 0


def test_engine_phase_spans_and_slo_sample():
    """One traced request yields the queue -> prefill -> decode phase
    timeline, every span parented under the replica span from the
    trace context, plus a TTFT/TPOT sample in the SLO ring and the
    ttft/tpot histograms."""
    tracing.clear_spans()
    tid, parent = "cd" * 8, "ee" * 8
    t_before = _hist_counts("ray_tpu_serve_ttft_seconds")
    eng = _engine()
    eng.add_request([1, 2, 3, 4, 5, 6], 4, trace_ctx=(tid, parent))
    _drain(eng)
    spans = [tracing.span_row_to_dict(r)
             for r in tracing.collect_spans_since(0)["rows"]]
    journey = {s["name"]: s for s in spans
               if s["name"].startswith("serve.")}
    assert {"serve.queue", "serve.prefill", "serve.decode"} \
        <= set(journey)
    for s in journey.values():
        assert s["trace_id"] == tid and s["parent_id"] == parent
        assert s["start"] <= s["end"]
    # Phases tile the request: queue ends where prefill starts, which
    # ends where decode starts.
    assert journey["serve.queue"]["end"] == \
        journey["serve.prefill"]["start"]
    assert journey["serve.prefill"]["end"] == \
        journey["serve.decode"]["start"]
    assert journey["serve.decode"]["attributes"]["tokens"] == 4
    # Cross-process clock alignment rides the queue span.
    assert "clock_off" in journey["serve.queue"]["attributes"]
    sample = eng.slo_samples[-1]
    assert sample["tokens"] == 4 and sample["ttft"] > 0
    assert sample["tpot"] >= 0 and "queue_wait" in sample
    t_after = _hist_counts("ray_tpu_serve_ttft_seconds")
    assert t_after.get((), 0) == t_before.get((), 0) + 1


def test_untraced_request_records_no_spans():
    """No trace context -> zero span-ring writes (the hot path stays
    clean for callers that did not opt in), but SLO samples and
    metrics still flow."""
    tracing.clear_spans()
    eng = _engine()
    eng.add_request([1, 2, 3, 4], 4)
    _drain(eng)
    spans = [tracing.span_row_to_dict(r)
             for r in tracing.collect_spans_since(0)["rows"]]
    assert not [s for s in spans if s["name"].startswith("serve.")]
    assert eng.slo_samples and eng.slo_samples[-1]["tokens"] == 4


def test_engine_step_sampler(monkeypatch):
    """RAY_TPU_SERVE_STEP_SAMPLE_EVERY=N snapshots occupancy every Nth
    step; 0 disables the sampler entirely."""
    monkeypatch.setenv("RAY_TPU_SERVE_STEP_SAMPLE_EVERY", "1")
    eng = _engine()
    eng.add_request([1, 2, 3, 4, 5], 4)
    _drain(eng)
    s = eng.engine_sample
    assert s is not None and s["step"] >= 1
    for key in ("ts", "active", "waiting", "free_pages",
                "inflight_chunks", "prefill_tokens", "completed"):
        assert key in s, s
    assert s["completed"] >= 0 and s["free_pages"] > 0

    monkeypatch.setenv("RAY_TPU_SERVE_STEP_SAMPLE_EVERY", "0")
    eng2 = _engine()
    eng2.add_request([1, 2, 3], 4)
    _drain(eng2)
    assert eng2.engine_sample is None


def test_server_stats_drain_slo_samples():
    """LLMServer.stats() hands the SLO sample ring to the load report
    exactly once (drain semantics): the controller probe must not
    double-count a window."""
    srv = _server()
    srv.generate([1, 2, 3, 4, 5], max_new_tokens=4)
    st = srv.stats()
    assert st["slo_samples"] and st["slo_samples"][-1]["tokens"] == 4
    assert "ttft" in st["slo_samples"][-1]
    st2 = srv.stats()
    assert "slo_samples" not in st2  # drained, not re-reported


# ---------------------------------------------------------------------------
# Controller: fold + sliding-window percentiles
# ---------------------------------------------------------------------------


def test_controller_slo_fold_and_percentiles(monkeypatch):
    from ray_tpu.serve.controller import (DeploymentTarget,
                                          ServeController)

    c = ServeController.__new__(ServeController)
    c._lock = threading.RLock()
    c._slo = {}
    tgt = DeploymentTarget(app_name="app", name="dep", blob=b"",
                           config={}, version="v1")
    now = time.time()
    # A stale sample ages out of the window (left-pruned).
    c._fold_slo(tgt, {"replica_id": "r0",
                      "slo_samples": [{"ttft": 9.0, "tpot": 9.0,
                                       "queue_wait": 9.0,
                                       "ts": now - 10_000}]})
    samples = [{"ttft": 0.1 * (i + 1), "tpot": 0.01,
                "queue_wait": 0.001, "tokens": 4, "ts": now}
               for i in range(20)]
    c._fold_slo(tgt, {"replica_id": "r1", "slo_samples": samples,
                      "engine_sample": {"ts": now, "active": 2,
                                        "free_pages": 60}})
    c._fold_slo(tgt, {"replica_id": "r1",
                      "slo_samples": [{"queue_wait": 0.5,
                                       "shed": "deadline", "ts": now}]})
    out = c.serve_slo()
    e = out["app/dep"]
    assert e["completed"] == 20 and e["shed"] == 1
    # Nearest-rank over 0.1..2.0: p50 = 10th value, p99 = the max —
    # and the stale 9.0 sample is gone.
    assert e["ttft"]["count"] == 20
    assert e["ttft"]["p50"] == pytest.approx(1.0)
    assert e["ttft"]["p99"] == pytest.approx(2.0)
    assert e["ttft"]["p50"] <= e["ttft"]["p95"] <= e["ttft"]["p99"]
    assert e["queue_wait"]["count"] == 21  # sheds attribute wait too
    assert e["engine"]["r1"]["active"] == 2
    # The window knob narrows the view.
    monkeypatch.setenv("RAY_TPU_SERVE_SLO_WINDOW_S", "0.000001")
    time.sleep(0.01)
    out = c.serve_slo()
    assert out["app/dep"]["completed"] == 0


# ---------------------------------------------------------------------------
# opsdump: per-request serve lanes
# ---------------------------------------------------------------------------


def test_opsdump_serve_request_lanes(tmp_path):
    from ray_tpu.util import journal

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import opsdump
    finally:
        sys.path.pop(0)
    js = journal.Journal(str(tmp_path), "spans", fsync_s=0.01)
    try:
        t0 = time.time()
        js.append(["s1", "", "t1aaaaaaaaaaaaaa", "serve.request",
                   t0, t0 + 2.0, {"route": "/gw"}])
        js.append(["s2", "s1", "t1aaaaaaaaaaaaaa", "serve.queue",
                   t0, t0 + 0.1, None])
        js.append(["s3", "s1", "t1aaaaaaaaaaaaaa", "serve.decode",
                   t0 + 0.5, t0 + 2.0, {"tokens": 5}])
        js.append(["s4", "", "t2bbbbbbbbbbbbbb", "serve.request",
                   t0 + 1.0, t0 + 1.5, None])
        js.append(["x1", "", "other", "step", t0, t0 + 0.5, None,
                   "w" * 8, 4242])
        assert js.flush(timeout=10)
    finally:
        js.close()
    evs = opsdump.build_trace(str(tmp_path), streams=("spans",))
    serve_evs = [e for e in evs if e.get("pid") == opsdump._SERVE_PID]
    lanes = {e["args"]["name"]: e["tid"] for e in serve_evs
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    # One named lane per trace id, first-seen request on lane 0.
    assert lanes == {"req t1aaaaaa": 0, "req t2bbbbbb": 1}
    slices = [(e["name"], e["tid"]) for e in serve_evs
              if e.get("ph") == "X"]
    assert ("serve.request", 0) in slices
    assert ("serve.queue", 0) in slices
    assert ("serve.decode", 0) in slices
    assert ("serve.request", 1) in slices
    # Phase args keep the span linkage for Perfetto's detail pane.
    dec = next(e for e in serve_evs if e.get("ph") == "X"
               and e["name"] == "serve.decode")
    assert dec["args"]["parent_id"] == "s1"
    assert dec["args"]["tokens"] == 5
    # Non-serve spans stay on their worker lane, untouched.
    worker = [e for e in evs if e.get("pid") == 4242
              and e.get("ph") == "X"]
    assert [e["name"] for e in worker] == ["step"]


# ---------------------------------------------------------------------------
# Cluster: connected trace over HTTP + /api/serve_slo + partial timeline
# ---------------------------------------------------------------------------


def _get_json(url):
    import urllib.request

    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_release_does_not_look_the_router_up_again():
    """A response's slot is released by a future's callback, on the RPC
    receive thread: it must go to the router that assigned it and never
    look one up (after serve.shutdown that is a blocking controller
    lookup, whose reply only that thread could receive: the cluster test
    below hung there when the killed replica's death arrived late)."""
    import threading

    from ray_tpu.serve.handle import DeploymentResponse

    released = []

    class _Router:
        def release(self, hex_id):
            released.append(hex_id)

    class _Handle:
        def _router(self):
            raise AssertionError("looked the router up from a callback")

    resp = object.__new__(DeploymentResponse)
    resp._handle, resp._lock = _Handle(), threading.Lock()
    resp._assigned_hex, resp._assigned_router = "abc", _Router()
    resp._released = False
    resp._release()
    resp._release()                     # idempotent
    assert released == ["abc"]


def test_cluster_journey_trace_slo_and_partial_timeline(
        tmp_path, monkeypatch):
    """End to end on a real local cluster with the ops journal on:

    1. An HTTP request carrying X-Serve-Trace through a disaggregated
       gateway (prefill pool -> KV handoff -> decode pool) yields ONE
       parent-linked trace spanning the proxy and both replica worker
       processes, visible at /api/trace.
    2. /api/serve_slo serves non-trivial sliding-window percentiles
       folded from the replicas' load reports.
    3. SIGKILL of a replica mid-request leaves the already-recorded
       phases (queue, prefill) in the durable ops journal — a partial
       timeline — while the never-reached phases stay absent.
    """
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import DisaggLLMClient, LLMServer
    from ray_tpu.state.api import list_actors
    from ray_tpu.util import journal

    ops_dir = str(tmp_path / "ops")
    monkeypatch.setenv("RAY_TPU_OPS_JOURNAL_DIR", ops_dir)
    monkeypatch.setenv("RAY_TPU_OPS_JOURNAL_FSYNC_S", "0.05")
    tracing.clear_spans()
    rt = ray_tpu.init(num_cpus=8)
    try:
        serve.start(proxy=True)
        kw = dict(config_kwargs={}, page_size=_PS, num_pages=64,
                  max_batch=4)
        pre_h = serve.run(
            LLMServer.options(role="prefill").bind(**kw),
            name="llm-pre", route_prefix=None)
        dec_h = serve.run(
            LLMServer.options(role="decode").bind(**kw),
            name="llm-dec", route_prefix=None)

        @serve.deployment
        class Gateway:
            def __init__(self, pre, dec):
                self.client = DisaggLLMClient(pre, dec, page_size=_PS,
                                              timeout_s=120)

            def __call__(self, request):
                body = request.json() or {}
                return {"tokens": self.client.generate(
                    body["prompt"],
                    max_new_tokens=int(body.get("max_new", 4)))}

        serve.run(Gateway.bind(pre_h, dec_h), name="gw",
                  route_prefix="/gw")
        addr = serve.proxy_address()
        tid = "abcdef0123456789"
        prompt = [int(x) for x in np.random.default_rng(9).integers(
            1, 250, size=2 * _PS + 3)]
        req = urllib.request.Request(
            addr + "/gw",
            data=json.dumps({"prompt": prompt, "max_new": 4}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Serve-Trace": tid})
        deadline = time.time() + 90
        while True:
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    payload = json.loads(resp.read())
                break
            except Exception:
                if time.time() > deadline:
                    raise
                time.sleep(0.3)
        assert payload["tokens"]

        # -- 1. one connected trace spanning proxy + both pools -------
        want = {"serve.request", "serve.replica", "serve.queue",
                "serve.prefill", "serve.decode"}
        deadline = time.time() + 45
        while True:
            reply = rt.core.client.call(
                {"op": "harvest_spans", "timeout_s": 15.0},
                timeout=30.0)
            mine = {s["span_id"]: s for s in reply["spans"]
                    if s.get("trace_id") == tid}
            for s in tracing.get_spans():  # proxy-local spans
                if s.get("trace_id") == tid:
                    mine.setdefault(s["span_id"], s)
            if want <= {s["name"] for s in mine.values()}:
                break
            assert time.time() < deadline, (
                f"incomplete trace: {sorted(set(s['name'] for s in mine.values()))}")
            time.sleep(0.3)
        # Parent-linked: every parent resolves inside the trace (the
        # adopted header id is the root, so exactly one parentless
        # chain head — the proxy's serve.request span).
        for s in mine.values():
            assert not s.get("parent_id") or s["parent_id"] in mine, s
        roots = [s for s in mine.values() if not s.get("parent_id")]
        assert [s["name"] for s in roots] == ["serve.request"]
        # ...and it spans multiple replica worker processes.
        workers = {s.get("worker") for s in mine.values()
                   if s.get("worker")}
        assert len(workers) >= 2, sorted(mine.values(),
                                         key=lambda s: s["start"])

        from ray_tpu.dashboard.http_head import Dashboard

        dash = Dashboard(rt)
        try:
            ev = _get_json(dash.url + "/api/trace")
            tr = [e for e in ev if e.get("ph") == "X"
                  and (e.get("args") or {}).get("trace_id") == tid]
            assert tr, "journey spans missing from /api/trace"

            # -- 2. per-deployment SLO percentiles ---------------------
            deadline = time.time() + 45
            while True:
                slo = _get_json(dash.url + "/api/serve_slo")
                good = {k: e for k, e in slo.items()
                        if e.get("completed", 0) >= 1 and "ttft" in e}
                if good:
                    break
                assert time.time() < deadline, slo
                time.sleep(0.3)
            key, e = next(iter(good.items()))
            assert "/" in key  # app/deployment attribution
            assert 0 < e["ttft"]["p50"] <= e["ttft"]["p99"]
            assert e["tpot"]["count"] >= 1
            assert e["queue_wait"]["count"] >= 1
            assert e["window_s"] > 0
        finally:
            dash.stop()

        # -- 3. SIGKILL mid-request: partial timeline in the journal --
        tid2 = "fedcba9876543210"
        slow_kw = dict(config_kwargs=dict(max_seq_len=4096),
                       page_size=_PS, num_pages=1100, max_batch=2,
                       multi_step=1)
        slow_h = serve.run(LLMServer.bind(**slow_kw), name="llm-slow",
                           route_prefix=None)
        # Warm the replica so the traced request spends its time
        # decoding, not compiling.
        assert slow_h.generate.remote([1, 2, 3], 4).result(
            timeout_s=300) is not None
        ctrl = serve.api._get_controller()
        entries = ray_tpu.get(ctrl.get_replicas.remote(
            "llm-slow", "llm_server"), timeout=30)
        pid = next(a["pid"] for a in list_actors()
                   if a["actor_id"] == entries[0]["actor_hex"]
                   and a.get("pid"))
        slow_h.options(trace_ctx=(tid2, "")).generate.remote(
            [5, 6, 7, 8], max_new_tokens=3500)
        deadline = time.time() + 120
        while True:  # wait for the prefill phase to be harvested
            reply = rt.core.client.call(
                {"op": "harvest_spans", "timeout_s": 10.0},
                timeout=30.0)
            names2 = {s["name"] for s in reply["spans"]
                      if s.get("trace_id") == tid2}
            if "serve.prefill" in names2:
                break
            assert time.time() < deadline, names2
            time.sleep(0.1)
        os.kill(pid, signal.SIGKILL)  # the decode never finishes here
        deadline = time.time() + 30
        while True:  # journal fsync is async; poll the disk
            rows = [tracing.span_row_to_dict(env["d"]) for env in
                    journal.replay(ops_dir, "spans")
                    if isinstance(env.get("d"), list)
                    and len(env["d"]) >= 7]
            mine2 = [r for r in rows if r["trace_id"] == tid2]
            if any(r["name"] == "serve.prefill" for r in mine2):
                break
            assert time.time() < deadline, \
                "journey spans never spilled to the journal"
            time.sleep(0.2)
        assert any(r["name"] == "serve.queue" for r in mine2)
        # The kill cut the journey short: the recorded prefix survives
        # in the journal, the never-reached phases do not.
        assert not any(r["name"] in ("serve.decode", "serve.replica")
                       for r in mine2)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        tracing.clear_spans()
