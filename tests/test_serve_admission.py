"""Serve data plane under load: engine admission control (bounded queue,
deadline shedding, abort reclamation, per-step prefill budget),
load-feedback P2C routing with staleness fallback, and the multiplex
model cache's concurrency guarantees.

These are unit tests — no cluster; the engine runs the tiny CPU config
and the router is exercised directly against an injected replica set.
"""

import threading
import time

import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.serve.llm_engine import LLMEngine, QueueFull


def _engine(**over):
    kw = dict(page_size=4, num_pages=64, max_batch=4,
              enable_prefix_caching=False, queue_timeout_s=0)
    kw.update(over)
    return LLMEngine(tfm.TransformerConfig.tiny(), **kw)


# ---------------------------------------------------------------------------
# Engine admission control
# ---------------------------------------------------------------------------


def test_admission_queue_full_backpressure():
    """Past max_queue, add_request raises QueueFull at the door — the
    one point where the caller can still retry another replica —
    instead of growing the waiting queue without bound."""
    eng = _engine(max_queue=2)
    eng.add_request([1, 2, 3], 4)
    eng.add_request([4, 5, 6], 4)
    with pytest.raises(QueueFull, match="cap 2"):
        eng.add_request([7, 8, 9], 4)
    assert eng.num_shed == 1
    assert len(eng.waiting) == 2  # the reject didn't enqueue


def test_admission_deadline_shed_on_burst():
    """Requests whose queueing deadline passes before they reach a slot
    are shed at the next step with reason 'deadline' (the waiter gets
    RequestShed through serve/llm.py, not an indefinite hang)."""
    eng = _engine(max_batch=2)
    ids = [eng.add_request([10 + i, 11 + i], 4, deadline_s=0.02)
           for i in range(3)]
    time.sleep(0.08)
    done = eng.step()
    assert done == {}
    assert not eng.waiting
    assert eng.num_shed == 3
    assert {rid: eng.shed[rid] for rid in ids} == \
        {rid: "deadline" for rid in ids}


def test_abort_frees_slot_and_kv_pages():
    """Mid-generation abort (the disconnect path) returns the slot and
    every KV page to the pool, and the engine keeps serving afterwards
    (dirty-slot cleanup doesn't poison later requests)."""
    eng = _engine(max_batch=2, num_pages=32)
    free0 = eng.allocator.num_free
    rid = eng.add_request([1, 2, 3, 4], 16)
    for _ in range(5):
        eng.step()
        if eng.num_active:
            break
    assert eng.num_active == 1
    assert eng.allocator.num_free < free0
    assert eng.abort(rid) is True
    assert eng.num_active == 0
    assert eng.allocator.num_free == free0
    assert eng.shed == {rid: "aborted"}
    assert eng.num_aborted == 1
    assert eng.abort(rid) is False  # already gone

    # The engine is still healthy: a follow-up request completes.
    eng.shed.clear()
    rid2 = eng.add_request([5, 6, 7], 4)
    done = {}
    for _ in range(100):
        done.update(eng.step())
        if rid2 in done:
            break
    assert len(done[rid2]) == 4


def test_prefill_budget_interleaves_admission():
    """With a per-step prefill token budget the engine admits a prompt
    burst over several steps (decode slots keep stepping in between);
    with the budget disabled the same burst seats in one wave."""
    prompts = [[i + 1, i + 2, i + 3, i + 4, i + 5, i + 6, i + 7, i + 8]
               for i in (0, 10, 20)]

    def steps_to_seat(eng):
        for p in prompts:
            eng.add_request(list(p), 32)
        for n in range(1, 10):
            eng.step()
            if eng.num_active == 3:
                return n
        return 10

    budgeted = steps_to_seat(_engine(prefill_budget=8))
    unbudgeted = steps_to_seat(_engine(prefill_budget=0))
    # 3 x 8-token prompts at 8 tokens/step: one admission per step.
    assert budgeted >= 3
    assert unbudgeted < budgeted


# ---------------------------------------------------------------------------
# Load-feedback routing (router.py): P2C over piggybacked reports
# ---------------------------------------------------------------------------

_HEX_A = "a" * 32
_HEX_B = "b" * 32


def _mk_router():
    """A Router wired to an injected replica set — no controller, no
    poll thread, no cluster; exactly the state assign_replica reads."""
    from ray_tpu.serve import router as router_mod

    r = router_mod.Router.__new__(router_mod.Router)
    r.app_name = "app"
    r.deployment = "dep"
    r._set = router_mod._ReplicaSet()
    s = r._set
    with s.cv:
        s.entries = [{"actor_hex": _HEX_A, "max_ongoing": 8},
                     {"actor_hex": _HEX_B, "max_ongoing": 8}]
        for e in s.entries:
            s.handles[e["actor_hex"]] = object()
            s.inflight.setdefault(e["actor_hex"], 0)
    return r


def test_router_fresh_feedback_steers_to_shallow_queue():
    r = _mk_router()
    r._set.update_reports({
        _HEX_A: {"queue_depth": 0, "free_kv_pages": 10},
        _HEX_B: {"queue_depth": 50, "free_kv_pages": 10},
    })
    for _ in range(10):
        hex_id, _ = r.assign_replica(timeout_s=1)
        assert hex_id == _HEX_A  # P2C always sees both; A's score wins
        r.release(hex_id)


def test_router_kv_exhaustion_penalty():
    """An exhausted KV pool outweighs a small queue: every admission
    there would stall on pages."""
    r = _mk_router()
    r._set.update_reports({
        _HEX_A: {"queue_depth": 0, "free_kv_pages": 0},
        _HEX_B: {"queue_depth": 2, "free_kv_pages": 64},
    })
    now = time.monotonic()
    a, b = r._set.entries
    assert r._score(a, now, 5.0) == (4.0, True)
    assert r._score(b, now, 5.0) == (2.0, True)
    hex_id, _ = r.assign_replica(timeout_s=1)
    assert hex_id == _HEX_B


def test_router_stale_feedback_falls_back_to_local_signal():
    """A report older than RAY_TPU_SERVE_FEEDBACK_STALE_S is ignored
    (fossil data from a wedged controller must not steer traffic); the
    blind local in-flight count decides instead."""
    r = _mk_router()
    r._set.update_reports({_HEX_B: {"queue_depth": 100}})
    r._set.reports[_HEX_B]["received_at"] -= 60.0  # age past staleness
    r._set.inflight[_HEX_A] = 5
    now = time.monotonic()
    b = r._set.entries[1]
    score, fresh = r._score(b, now, 5.0)
    assert (score, fresh) == (0.0, False)  # depth-100 report ignored
    hex_id, _ = r.assign_replica(timeout_s=1)
    assert hex_id == _HEX_B


def test_router_model_affinity_prefers_loaded_replica():
    """A fresh report listing the requested multiplex model restricts
    the P2C pool to replicas that skip the cold load; once the report
    goes stale the affinity bias disappears."""
    r = _mk_router()
    r._set.update_reports({
        _HEX_A: {"queue_depth": 0, "models": []},
        _HEX_B: {"queue_depth": 3, "models": ["m1"]},
    })
    r._set.inflight[_HEX_B] = 3
    hex_id, _ = r.assign_replica(timeout_s=1, model_id="m1")
    assert hex_id == _HEX_B  # affinity beats the load gap
    r.release(hex_id)

    now = time.monotonic()
    b = r._set.entries[1]
    assert r._has_model(b, "m1", now, 5.0)
    r._set.reports[_HEX_B]["received_at"] -= 60.0
    assert not r._has_model(b, "m1", now, 5.0)


def test_router_staleness_knob(monkeypatch):
    from ray_tpu.serve.router import _stale_s

    monkeypatch.setenv("RAY_TPU_SERVE_FEEDBACK_STALE_S", "2.5")
    assert _stale_s() == 2.5
    monkeypatch.setenv("RAY_TPU_SERVE_FEEDBACK_STALE_S", "bogus")
    assert _stale_s() == 5.0


# ---------------------------------------------------------------------------
# Multiplex model cache: single-flight loads, pinned models never evict
# ---------------------------------------------------------------------------


def test_model_cache_single_flight_concurrent_loads():
    from ray_tpu.serve.multiplex import _ModelCache

    loads = []

    def loader(mid):
        loads.append(mid)
        time.sleep(0.2)  # wide window for racers to pile in
        return {"id": mid}

    cache = _ModelCache(loader, capacity=2)
    out = []
    lock = threading.Lock()

    def hit():
        m = cache.get(None, "m1")
        with lock:
            out.append(m)

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert len(out) == 8
    assert cache.load_count == 1 and loads == ["m1"]
    assert all(m is out[0] for m in out)  # one object, shared


def test_model_cache_never_evicts_pinned_model():
    from ray_tpu.serve.multiplex import _ModelCache

    class Model:
        def __init__(self):
            self.unloaded = False

        def unload(self):
            self.unloaded = True

    cache = _ModelCache(lambda mid: Model(), capacity=1)
    m1 = cache.get(None, "m1")  # pinned by the get
    m2 = cache.get(None, "m2")  # over capacity, but m1 is in use
    assert set(cache.loaded_ids()) == {"m1", "m2"}  # overflow, no evict
    assert not m1.unloaded
    cache.unpin("m1")  # request finished -> deferred eviction runs
    assert cache.loaded_ids() == ["m2"]
    assert m1.unloaded and not m2.unloaded
    assert cache.pinned_ids() == ["m2"]


def test_model_cache_failed_load_retries_fresh():
    from ray_tpu.serve.multiplex import _ModelCache

    calls = {"n": 0}

    def loader(mid):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("flaky checkpoint")
        return mid.upper()

    cache = _ModelCache(loader, capacity=2)
    with pytest.raises(ValueError, match="flaky checkpoint"):
        cache.get(None, "m")
    assert cache.get(None, "m") == "M"  # no poisoned loading marker


# ---------------------------------------------------------------------------
# Serve observability: metrics + flight-recorder "serve" lane
# ---------------------------------------------------------------------------


def test_serve_metrics_and_flight_recorder_lane():
    """Admission decisions are observable: the serve counter/gauge
    series show up in the local metric snapshots (so /metrics exports
    them) and the flight recorder's "serve" lane records the
    queue_full / shed / abort decisions."""
    from ray_tpu.util import flight_recorder
    from ray_tpu.util.metrics import local_snapshots

    flight_recorder.configure(enable=True)
    flight_recorder.clear()
    eng = _engine(max_queue=1, max_batch=2)
    eng.add_request([1, 2], 4)
    with pytest.raises(QueueFull):
        eng.add_request([3, 4], 4)
    names = {s["name"] for s in local_snapshots()}
    assert {"ray_tpu_serve_requests_total", "ray_tpu_serve_shed_total",
            "ray_tpu_serve_queue_depth"} <= names
    events = [(e["category"], e["event"])
              for e in flight_recorder.dump(last=50)]
    assert ("serve", "queue_full") in events
