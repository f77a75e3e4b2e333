"""Disaggregated prefill/decode serving: KV-page handoff between
engines/replicas, role-aware + prefix-locality routing, and graceful
degradation (empty role pools, stale digests, failed handoff pulls —
a handoff failure is slower, never lost).

Engine and router layers are unit tests (no cluster); the chaos test
at the bottom runs the two-pool flow on a real local cluster and
SIGKILLs the prefill replica mid-run.
"""

import os
import signal
import time

import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.serve.llm_engine import LLMEngine, PrefixCache

_PS = 4  # page size for every tiny engine here


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


# ---------------------------------------------------------------------------
# Engine: export at finish + import splice-in
# ---------------------------------------------------------------------------


def test_engine_kv_roundtrip_token_exact():
    """prefill on engine A -> bundle -> import into engine B resumes
    with byte-identical KV: B's continuation equals a single mixed
    engine's generation for the same prompt, token for token."""
    pre, dec, ref = _engine(), _engine(), _engine()
    prompt = [5, 9, 2, 7, 3, 8, 1, 6, 4, 2, 9]
    rid = pre.add_request(prompt, 1, export_on_finish=True)
    done = _drain(pre)
    bundle = pre.kv_ready.pop(rid)
    assert bundle["op"] == "serve_kv_export"
    assert bundle["generated"] == done[rid]
    # context invariant: KV exists for prompt + all generated tokens
    # but the last (whose KV is written by the NEXT step)
    assert bundle["context_len"] == \
        len(prompt) + len(bundle["generated"]) - 1
    rid2 = dec.import_kv(bundle, max_new_tokens=8)
    got = _drain(dec)[rid2]
    want = ref.generate([prompt], max_new_tokens=8)[0]
    assert got == want
    assert pre.kv_exports == 1 and dec.kv_imports == 1


def test_export_at_finish_never_races_fast_requests():
    """A request that completes inside one engine step still yields a
    bundle: the capture happens in _maybe_finish before the pages are
    freed, not from a polling thread."""
    eng = _engine(multi_step=4)
    rid = eng.add_request([1, 2, 3, 4, 5], 1, export_on_finish=True)
    _drain(eng)
    assert rid in eng.kv_ready
    assert eng.kv_ready[rid]["generated"]


def test_import_rejects_incompatible_bundles():
    """Geometry mismatches fail loudly at import (the caller falls
    back to re-prefill); a half-spliced cache would decode garbage."""
    pre, dec = _engine(), _engine(page_size=8)
    rid = pre.add_request([1, 2, 3, 4, 5, 6], 1, export_on_finish=True)
    _drain(pre)
    bundle = pre.kv_ready.pop(rid)
    with pytest.raises(ValueError, match="page_size"):
        dec.import_kv(bundle, max_new_tokens=4)
    bad = dict(bundle, context_len=bundle["context_len"] + 3)
    with pytest.raises(ValueError, match="context_len"):
        _engine().import_kv(bad, max_new_tokens=4)


def test_import_registers_pages_in_local_prefix_cache():
    """Imported prompt pages land in the DECODE engine's prefix cache:
    the second handoff sharing the system prompt splices nothing it
    already holds and counts a hit (cross-replica cache reuse)."""
    pre, dec = _engine(), _engine()
    sys_prompt = [11, 12, 13, 14, 15, 16, 17, 18]  # 2 full pages
    for i, tail in enumerate(([1, 2, 3], [4, 5, 6], [7, 8, 9])):
        rid = pre.add_request(sys_prompt + tail, 1,
                              export_on_finish=True)
        _drain(pre)
        rid2 = dec.import_kv(pre.kv_ready.pop(rid), max_new_tokens=4)
        _drain(dec)
    assert dec.kv_imports == 3
    assert dec.prefix_cache.hits >= 2
    assert dec.prefix_cache.tokens_saved >= 2 * len(sys_prompt)


def test_prefix_digest_shape():
    """digest() returns truncated-hex keys, hottest (refcount, then
    shallowest) first, capped at k — the router matches prefix_hint
    against exactly this encoding."""
    eng = _engine()
    eng.generate([[21, 22, 23, 24, 25, 26, 27, 28, 29]],
                 max_new_tokens=2)
    d = eng.prefix_cache.digest(16)
    assert d and all(len(k) == 16 for k in d)
    full = 9 // _PS
    chain = PrefixCache.chain_hashes([21, 22, 23, 24, 25, 26, 27,
                                      28, 29], _PS, full)
    assert set(k.hex()[:16] for k in chain) <= set(d)
    assert eng.prefix_cache.digest(1) == d[:1]


# ---------------------------------------------------------------------------
# Server layer: prefill_only / decode_from, fallback never loses work
# ---------------------------------------------------------------------------


def test_server_handoff_cross_replica_hits_and_exactness():
    pre, dec, ref = _server(), _server(), _server()
    rng = np.random.default_rng(1)
    sys_prompt = [int(x) for x in rng.integers(1, 250, size=2 * _PS)]
    for _ in range(4):
        prompt = sys_prompt + [int(x)
                               for x in rng.integers(1, 250, size=3)]
        kv = pre.prefill_only(prompt, max_new_tokens=8)
        got = dec.decode_from(prompt, kv, max_new_tokens=8)
        want = ref._submit_and_wait([prompt], 8, 0.0)[0]
        assert got == want
    assert dec.engine.kv_imports == 4
    assert dec.handoff_fallbacks == 0
    assert dec.engine.prefix_cache.hits >= 3
    assert pre.engine.kv_exports == 4
    st = dec.stats()
    assert st["kv_imports"] == 4 and st["handoff_fallbacks"] == 0
    assert st["prefix_digest"]["op"] == "serve_prefix_digest"


def test_server_done_at_prefill_short_circuits():
    pre, dec, ref = _server(), _server(), _server()
    p = [3, 1, 4, 1, 5, 9, 2, 6]
    kv = pre.prefill_only(p, max_new_tokens=1)
    assert kv.get("done") is not None and len(kv["done"]) == 1
    got = dec.decode_from(p, kv, max_new_tokens=1)
    assert got == kv["done"] == ref._submit_and_wait([p], 1, 0.0)[0]
    assert dec.engine.kv_imports == 0  # no pages rode the wire


def test_server_fallback_on_bad_bundle_keeps_request():
    """An unusable bundle (corrupt geometry) re-prefills locally: the
    caller still gets the right tokens; the fallback is counted."""
    dec, ref = _server(), _server()
    p = [7, 7, 7, 2, 2, 2, 9, 9]
    bad = {"op": "serve_kv_export", "req": 0, "prompt": p,
           "generated": [5], "context_len": 999, "page_size": _PS,
           "num_layers": 1, "kd": 2, "dtype": "float32",
           "k": np.zeros((1, 1, _PS, 2)), "v": np.zeros((1, 1, _PS, 2))}
    got = dec.decode_from(p, bad, max_new_tokens=4)
    assert got == ref._submit_and_wait([p], 4, 0.0)[0]
    assert dec.handoff_fallbacks == 1


def test_server_fallback_on_unpullable_ref():
    """A serve_kv_import pointer that cannot be resolved (no cluster
    runtime holds the object) degrades to re-prefill, not an error."""
    dec, ref = _server(), _server()
    p = [8, 6, 7, 5, 3, 0, 9]
    kv = {"op": "serve_kv_import", "obj": "ab" * 14, "size": 128}
    got = dec.decode_from(p, kv, max_new_tokens=4)
    assert got == ref._submit_and_wait([p], 4, 0.0)[0]
    assert dec.handoff_fallbacks == 1


# ---------------------------------------------------------------------------
# Request-journey tracing across the two legs
# ---------------------------------------------------------------------------


def _install_request_ctx(trace_id, parent, span_id):
    """Simulate the replica data-plane prologue: a live RequestContext
    with the proxy's trace ctx and this call's pre-allocated span."""
    import ray_tpu.serve.replica as replica_mod

    ctx = replica_mod.RequestContext(trace_ctx=(trace_id, parent))
    ctx.span_id = span_id
    replica_mod._replica_context.request = ctx
    return ctx


def test_two_leg_handoff_yields_one_connected_trace():
    """prefill_only on server A and decode_from on server B, each under
    its own (simulated) replica request context: every phase span lands
    in ONE trace, and the decode side's handoff_pull span parents under
    the PREFILL replica's span carried inside the bundle — the
    cross-process link that makes a disaggregated request render as a
    single tree instead of two orphaned fragments."""
    import ray_tpu.serve.replica as replica_mod
    from ray_tpu.util import tracing

    tracing.clear_spans()
    tid = "ab" * 8
    pre_span, dec_span = "11" * 8, "22" * 8
    pre, dec = _server(), _server()
    rng = np.random.default_rng(5)
    prompt = [int(x) for x in rng.integers(1, 250, size=2 * _PS + 3)]
    try:
        _install_request_ctx(tid, "00" * 8, pre_span)
        kv = pre.prefill_only(prompt, max_new_tokens=8)
        assert kv.get("trace") == [tid, pre_span]  # rides the bundle
        _install_request_ctx(tid, "00" * 8, dec_span)
        got = dec.decode_from(prompt, kv, max_new_tokens=8)
        assert got
    finally:
        replica_mod._replica_context.request = None
    spans = [tracing.span_row_to_dict(r)
             for r in tracing.collect_spans_since(0)["rows"]]
    journey = [s for s in spans if s["name"].startswith("serve.")]
    assert journey and {s["trace_id"] for s in journey} == {tid}
    names = [s["name"] for s in journey]
    for phase in ("serve.queue", "serve.prefill", "serve.import",
                  "serve.decode"):
        assert phase in names, f"missing {phase} in {names}"
    # Each leg's engine phases parent under that leg's replica span.
    assert {s["parent_id"] for s in journey} <= {pre_span, dec_span}
    # Both legs contributed phases (two queue spans, one per engine).
    assert names.count("serve.queue") == 2

    # Pointer path: the handoff pull span parents under the prefill
    # leg's span carried IN the payload — even when the pull fails
    # (no object plane here), so a broken handoff still shows up on
    # the request's timeline as a failed pull + local re-prefill.
    tracing.clear_spans()
    ptr = {"op": "serve_kv_import", "obj": "ab" * 14, "size": 64,
           "trace": [tid, pre_span]}
    try:
        _install_request_ctx(tid, "00" * 8, dec_span)
        got = dec.decode_from(prompt, ptr, max_new_tokens=4)
        assert got  # fallback re-prefill kept the request
    finally:
        replica_mod._replica_context.request = None
    spans = [tracing.span_row_to_dict(r)
             for r in tracing.collect_spans_since(0)["rows"]]
    pull = next(s for s in spans if s["name"] == "serve.handoff_pull")
    assert pull["parent_id"] == pre_span  # linked across the legs
    assert pull["trace_id"] == tid
    assert pull["attributes"]["ok"] is False
    assert "clock_off" in pull["attributes"]


def test_trace_ctx_survives_pointer_handoff():
    """The object-plane pointer path (serve_kv_import) carries the same
    trace linkage as the inline bundle: wire_schema admits it and the
    importing engine's splice spans join the prefill leg's trace."""
    from ray_tpu.core import wire_schema

    wire_schema.validate({"op": "serve_kv_import", "obj": "ab" * 14,
                          "size": 4096, "trace": ["cd" * 8, "ef" * 8]})
    wire_schema.validate({"op": "serve_kv_import", "obj": "ab" * 14,
                          "size": 4096})  # pre-tracing peers still valid
    with pytest.raises(wire_schema.SchemaError):
        wire_schema.validate({"op": "serve_kv_import", "obj": "ab" * 14,
                              "size": 4096, "trace": "not-a-list"})


# ---------------------------------------------------------------------------
# Wire schema + config surface
# ---------------------------------------------------------------------------


def test_wire_schema_declares_handoff_ops():
    from ray_tpu.core import wire_schema

    wire_schema.validate({"op": "serve_kv_import",
                          "obj": "ab" * 14, "size": 4096})
    wire_schema.validate({"op": "serve_prefix_digest",
                          "keys": ["aa" * 8]})
    with pytest.raises(wire_schema.SchemaError):
        wire_schema.validate({"op": "serve_kv_import", "size": 1})


def test_deployment_role_config():
    from ray_tpu.serve.config import DeploymentConfig
    from ray_tpu.serve.deployment import deployment

    assert DeploymentConfig().role == "mixed"
    with pytest.raises(ValueError, match="role"):
        DeploymentConfig(role="bogus")

    @deployment(role="prefill")
    class D:
        pass

    assert D.config.role == "prefill"
    assert D.options(role="decode").config.role == "decode"
    assert D.options(num_replicas=2).config.role == "prefill"


# ---------------------------------------------------------------------------
# Router: role pools, prefix locality, degradation
# ---------------------------------------------------------------------------

_HEX_P = "a" * 32
_HEX_D = "b" * 32
_HEX_M = "c" * 32


def _mk_router(entries):
    from ray_tpu.serve import router as router_mod

    r = router_mod.Router.__new__(router_mod.Router)
    r.app_name = "app"
    r.deployment = "dep"
    r._set = router_mod._ReplicaSet()
    s = r._set
    with s.cv:
        s.entries = entries
        for e in s.entries:
            s.handles[e["actor_hex"]] = object()
            s.inflight.setdefault(e["actor_hex"], 0)
    return r


def _roles3():
    return [{"actor_hex": _HEX_P, "max_ongoing": 8, "role": "prefill"},
            {"actor_hex": _HEX_D, "max_ongoing": 8, "role": "decode"},
            {"actor_hex": _HEX_M, "max_ongoing": 8, "role": "mixed"}]


def test_router_phase_restricts_to_role_pool():
    r = _mk_router(_roles3())
    for _ in range(20):
        hex_id, _ = r.assign_replica(timeout_s=1, phase="prefill")
        assert hex_id in (_HEX_P, _HEX_M)  # never the decode replica
        r.release(hex_id)
        hex_id, _ = r.assign_replica(timeout_s=1, phase="decode")
        assert hex_id in (_HEX_D, _HEX_M)
        r.release(hex_id)


def test_router_empty_pool_degrades_to_mixed_routing():
    """No replica of the requested role: the request still routes
    (graceful degradation) instead of timing out."""
    r = _mk_router([{"actor_hex": _HEX_D, "max_ongoing": 8,
                     "role": "decode"}])
    hex_id, _ = r.assign_replica(timeout_s=1, phase="prefill")
    assert hex_id == _HEX_D
    r.release(hex_id)


def test_router_strict_mode_waits_for_role_pool(monkeypatch):
    monkeypatch.setenv("RAY_TPU_SERVE_ROLE_STRICT", "1")
    r = _mk_router([{"actor_hex": _HEX_D, "max_ongoing": 8,
                     "role": "decode"}])
    with pytest.raises(TimeoutError):
        r.assign_replica(timeout_s=0.3, phase="prefill")


def test_router_entries_without_role_behave_as_mixed():
    """Pre-disagg controllers publish entries with no role key: they
    qualify for every phase (wire compatibility)."""
    r = _mk_router([{"actor_hex": _HEX_M, "max_ongoing": 8}])
    for phase in ("", "prefill", "decode"):
        hex_id, _ = r.assign_replica(timeout_s=1, phase=phase)
        assert hex_id == _HEX_M
        r.release(hex_id)


def test_router_prefix_locality_steers_prefill():
    """The replica whose hot-prefix digest longest-matches the
    request's hint wins even against a lighter queue elsewhere."""
    r = _mk_router(_roles3()[:2] + [
        {"actor_hex": _HEX_M, "max_ongoing": 8, "role": "prefill"}])
    hint = ["k1", "k2", "k3"]
    r._set.update_reports({
        _HEX_P: {"queue_depth": 2,
                 "prefix_digest": {"op": "serve_prefix_digest",
                                   "keys": ["k1", "k2"]}},
        _HEX_M: {"queue_depth": 0,
                 "prefix_digest": {"op": "serve_prefix_digest",
                                   "keys": ["zz"]}},
    })
    for _ in range(10):
        hex_id, _ = r.assign_replica(timeout_s=1, phase="prefill",
                                     prefix_keys=hint)
        assert hex_id == _HEX_P
        r.release(hex_id)
    # locality only biases PREFILL: decode ignores the hint
    hex_id, _ = r.assign_replica(timeout_s=1, phase="decode",
                                 prefix_keys=hint)
    assert hex_id in (_HEX_D,)
    r.release(hex_id)


def test_router_stale_digest_ignored():
    """A digest older than RAY_TPU_SERVE_FEEDBACK_STALE_S must not
    steer: the cache it describes has moved on."""
    r = _mk_router(_roles3())
    r._set.update_reports({
        _HEX_P: {"prefix_digest": {"op": "serve_prefix_digest",
                                   "keys": ["k1"]}}})
    e = r._set.entries[0]
    now = time.monotonic()
    assert r._prefix_match(e, ["k1"], now, 5.0) == 1
    r._set.reports[_HEX_P]["received_at"] -= 60.0
    assert r._prefix_match(e, ["k1"], now, 5.0) == 0


def test_router_decode_free_kv_tiebreak():
    """Equal queues: decode routes to the replica with more free KV
    pages (the imported context must fit).  The bonus is a tie-break —
    it never outweighs a whole queued request."""
    r = _mk_router(_roles3()[:2] + [
        {"actor_hex": _HEX_M, "max_ongoing": 8, "role": "decode"}])
    r._set.update_reports({
        _HEX_D: {"queue_depth": 0, "free_kv_pages": 2},
        _HEX_M: {"queue_depth": 0, "free_kv_pages": 500},
    })
    for _ in range(10):
        hex_id, _ = r.assign_replica(timeout_s=1, phase="decode")
        assert hex_id == _HEX_M
        r.release(hex_id)
    now = time.monotonic()
    d, m = r._set.entries[1], r._set.entries[2]
    # the existing no-phase scoring is untouched
    assert r._score(d, now, 5.0) == (0.0, True)
    sd, _ = r._score(d, now, 5.0, "decode")
    sm, _ = r._score(m, now, 5.0, "decode")
    assert sm < sd < 0.5  # bonus magnitude stays sub-request


# ---------------------------------------------------------------------------
# Cluster: two role pools + chaos kill of the prefill replica
# ---------------------------------------------------------------------------


def test_cluster_disagg_pools_with_prefill_chaos_kill():
    """End to end on a real local cluster: prefill-pool replica ->
    object-plane KV bundle -> decode-pool replica, prefix-locality
    routed.  Then SIGKILL the prefill replica's worker process: the
    DisaggLLMClient's next request degrades to decode-only generation
    (counted fallback) — a dead prefill pool never loses a request."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import DisaggLLMClient, LLMServer
    from ray_tpu.state.api import list_actors

    ray_tpu.init(num_cpus=8)
    try:
        kw = dict(config_kwargs={}, page_size=_PS, num_pages=64,
                  max_batch=4)
        pre_h = serve.run(
            LLMServer.options(role="prefill").bind(**kw),
            name="llm-prefill", route_prefix=None)
        dec_h = serve.run(
            LLMServer.options(role="decode").bind(**kw),
            name="llm-decode", route_prefix=None)
        client = DisaggLLMClient(pre_h, dec_h, page_size=_PS,
                                 timeout_s=120)
        rng = np.random.default_rng(7)
        sys_prompt = [int(x)
                      for x in rng.integers(1, 250, size=2 * _PS)]
        ref = LLMServer.func_or_class(page_size=_PS, num_pages=64,
                                      max_batch=4)
        for _ in range(3):
            prompt = sys_prompt + [
                int(x) for x in rng.integers(1, 250, size=3)]
            got = client.generate(prompt, max_new_tokens=8)
            assert got == ref._submit_and_wait([prompt], 8, 0.0)[0]
        assert client.handoffs == 3 and client.fallbacks == 0

        # chaos: SIGKILL the prefill replica's worker process.  The
        # data plane may recover transparently (handle retry through a
        # restarted replica) or the client may fall back to
        # decode-only — either way the request completes correctly.
        ctrl = serve.api._get_controller()
        entries = ray_tpu.get(ctrl.get_replicas.remote(
            "llm-prefill", "llm_server"), timeout=30)
        assert entries and entries[0].get("role") == "prefill"
        target_hex = entries[0]["actor_hex"]
        pid = next(a["pid"] for a in list_actors()
                   if a["actor_id"] == target_hex and a.get("pid"))
        os.kill(pid, signal.SIGKILL)

        prompt = sys_prompt + [9, 9, 9]
        got = client.generate(prompt, max_new_tokens=8)
        assert got == ref._submit_and_wait([prompt], 8, 0.0)[0]

        # prefill pool gone entirely: the client degrades to
        # decode-only generation and counts the fallback.
        serve.delete("llm-prefill")
        client2 = DisaggLLMClient(
            pre_h.options(assign_timeout_s=2), dec_h,
            page_size=_PS, timeout_s=120)
        prompt = sys_prompt + [4, 4, 4]
        got = client2.generate(prompt, max_new_tokens=8)
        assert got == ref._submit_and_wait([prompt], 8, 0.0)[0]
        assert client2.fallbacks == 1 and client2.handoffs == 0
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
