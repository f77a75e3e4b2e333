"""Serve data-plane benchmark: the admission-controlled LLM engine under
four-digit client counts.

Run: python scripts/bench_serve.py  (writes one JSON line to stdout;
results committed as SERVE_BENCH.json).

Three phases, all through the same admission-controlled engine the serve
replicas run (serve/llm_engine.py):

  sustained_load   1k+ concurrent synthetic clients (each client = one
                   outstanding request awaiting its stream) against one
                   engine: TTFT/TPOT p50/p99 INCLUDING queueing delay,
                   aggregate tok/s and the bandwidth-roofline fraction
                   (bench_decode math: HBM_BW / (weight_bytes + avg live
                   KV bytes) x batch).  The whole-run fraction is the
                   headline — at 8x bench_decode's request count the
                   prefill/drain edge effects amortize, which is the
                   point of serving at scale.
  burst_shed       a burst of 4x the queue cap with a tight deadline:
                   admission raises QueueFull at the door, the deadline
                   sheds queued stragglers at the next step, and every
                   ADMITTED request still completes.  Reports the shed
                   rate and its queue_full/deadline split.
  prefill_interference
                   decode TPOT p99 for long-generation requests with a
                   continuous stream of prompt prefills arriving vs the
                   same decoders alone.  The per-step prefill token
                   budget (RAY_TPU_SERVE_PREFILL_BUDGET) is what keeps
                   the ratio near 1: admission work interleaves in
                   bounded chunks instead of stalling live slots for a
                   full wave.
  tracing_overhead paired tracing-on/off rows: the same workload with
                   and without a request-journey trace context on every
                   request (queue/prefill/decode phase spans recorded
                   into the in-process ring).  The tok/s delta is the
                   cost of the observability path; tests pin it small.
  disaggregated    (--disagg) paired mixed-vs-disaggregated rows: the
                   same interference workload with the prefill stream
                   on a separate engine (decode TPOT on the decode
                   engine's busy clock), plus a cross-replica
                   prefix-cache phase that hands KV bundles from a
                   prefill server to a decode server and reports the
                   decode side's prefix hit rate + token-exactness.

Honesty rules (bench_decode's): TPU shapes only run on a real TPU
(devices[0].platform == "tpu"); elsewhere the tiny-config CPU fallback
runs the same code paths and says so in the artifact.  TTFT is
add_request -> first token on the host; TPOT is (last - first)/(n-1)
per request; queueing time is NOT excluded from TTFT — a shed-free
queue under load is the admission scheduler's job, not the clock's.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def _mk_engine(config, shape, **over):
    from ray_tpu.serve.llm_engine import LLMEngine

    kw = dict(page_size=shape["page_size"], num_pages=shape["num_pages"],
              max_batch=shape["max_batch"], multi_step=shape["multi_step"],
              max_queue=shape.get("max_queue", 4096),
              queue_timeout_s=0, prefill_budget=shape["prefill_budget"])
    kw.update(over)
    return LLMEngine(config, **kw)


def _warmup(eng, config, shape, rng):
    """Compile everything the measured loop hits: the packed admission
    wave, the decode chunk per context bucket, and the dirty-slot
    merge (mid-run admission while old slots finish)."""
    warm = [rng.integers(1, config.vocab_size,
                         shape["prompt_len"]).tolist()
            for _ in range(shape["max_batch"])]
    eng.generate(warm, max_new_tokens=shape["max_new"])
    eng.add_request(warm[0], max_new_tokens=shape["max_new"])
    eng.step()
    eng.add_request(warm[1], max_new_tokens=4)
    while eng.has_work():
        eng.step()


def _drive(eng, ids, t_add):
    """Step the engine to completion, timestamping first/last tokens."""
    results, t_first, t_done = {}, {}, {}
    steps = 0
    while eng.has_work():
        done = eng.step()
        now = time.perf_counter()
        steps += 1
        results.update(done)
        for rid in done:
            t_done[rid] = now
        for r in eng.slot_req:
            if r is not None and r.generated and r.req_id not in t_first:
                t_first[r.req_id] = now
        for rid in done:
            t_first.setdefault(rid, now)
    return results, t_first, t_done, steps


def run_sustained(config, shape, hbm_gb_s):
    from ray_tpu.models import transformer as tfm

    eng = _mk_engine(config, shape)
    rng = np.random.default_rng(0)
    _warmup(eng, config, shape, rng)

    n = shape["n_clients"]
    prompts = [rng.integers(1, config.vocab_size,
                            shape["prompt_len"]).tolist()
               for _ in range(n)]
    t0 = time.perf_counter()
    t_add, ids = {}, []
    for p in prompts:
        rid = eng.add_request(p, max_new_tokens=shape["max_new"])
        t_add[rid] = time.perf_counter()
        ids.append(rid)
    results, t_first, t_done, steps = _drive(eng, ids, t_add)
    dt = time.perf_counter() - t0
    assert set(ids) <= set(results), "missing results"
    gen_tokens = sum(len(results[i]) for i in ids)

    weight_bytes = 2 * tfm.num_params(config)
    kv_per_token = (2 * config.num_layers * config.num_kv_heads
                    * config.head_dim_ * 2)
    avg_ctx = shape["prompt_len"] + shape["max_new"] / 2
    kv_bytes = shape["max_batch"] * avg_ctx * kv_per_token
    tok_s = gen_tokens / dt
    roofline = {}
    if hbm_gb_s is not None:  # None: --rehearse, no device to bound
        roofline_tok_s = hbm_gb_s / (weight_bytes + kv_bytes) \
            * shape["max_batch"]
        frac = tok_s / roofline_tok_s
        roofline = {"roofline_tokens_per_sec": round(roofline_tok_s, 1),
                    "roofline_fraction": frac,
                    "roofline_fraction_pct": frac * 100.0}
        print(f"sustained: {tok_s:.3e} tok/s vs roofline "
              f"{roofline_tok_s:.3e} tok/s (fraction {frac:.3e})",
              file=sys.stderr)
    ttft = [t_first[i] - t_add[i] for i in ids]
    tpot = [(t_done[i] - t_first[i]) / (len(results[i]) - 1)
            for i in ids if len(results[i]) > 1]
    return {
        "concurrent_clients": n,
        "tokens_per_sec": round(tok_s, 1),
        **roofline,
        "ttft_p50_s": round(_pct(ttft, 50), 4),
        "ttft_p99_s": round(_pct(ttft, 99), 4),
        "tpot_p50_ms": round(_pct(tpot, 50) * 1e3, 3),
        "tpot_p99_ms": round(_pct(tpot, 99) * 1e3, 3),
        "generated_tokens": gen_tokens,
        "shed": eng.num_shed,
        "wall_s": round(dt, 2),
        "engine_steps": steps,
        "seq": f"{shape['prompt_len']}+{shape['max_new']}",
        "max_batch": shape["max_batch"],
    }


def run_burst_shed(config, shape):
    from ray_tpu.serve.llm_engine import QueueFull

    cap = 2 * shape["max_batch"]
    eng = _mk_engine(config, shape, max_queue=cap)
    rng = np.random.default_rng(1)
    _warmup(eng, config, shape, rng)

    burst = 4 * cap
    admitted, queue_full = [], 0
    deadline_s = shape["burst_deadline_s"]
    for _ in range(burst):
        p = rng.integers(1, config.vocab_size,
                         shape["prompt_len"]).tolist()
        try:
            admitted.append(eng.add_request(
                p, max_new_tokens=shape["max_new"],
                deadline_s=deadline_s))
        except QueueFull:
            queue_full += 1
    results, _, _, _ = _drive(eng, admitted, {})
    deadline_shed = sum(1 for i in admitted if i not in results)
    completed = sum(1 for i in admitted if i in results)
    shed = queue_full + deadline_shed
    return {
        "burst_clients": burst,
        "queue_cap": cap,
        "queue_full_rejects": queue_full,
        "deadline_sheds": deadline_shed,
        "completed": completed,
        "shed_rate": round(shed / burst, 3),
        "deadline_s": deadline_s,
    }


def run_prefill_interference(config, shape):
    """Decode TPOT p99 for long decoders, alone vs under a continuous
    prefill stream admitted within the per-step budget."""
    rng = np.random.default_rng(2)
    n_dec = max(2, shape["max_batch"] // 2)
    dec_prompts = [rng.integers(1, config.vocab_size,
                                shape["prompt_len"]).tolist()
                   for _ in range(n_dec)]

    def _measure(interfere):
        eng = _mk_engine(config, shape)
        # Full-shape warmup: the long generation walks context buckets
        # the short warmup never reaches, and the interference prompts
        # have their own prefill bucket — every compile must land here,
        # not in (only) the first measured run.
        eng.generate(dec_prompts,
                     max_new_tokens=shape["interf_max_new"])
        eng.generate([rng.integers(
            1, config.vocab_size,
            shape["interf_prompt_len"]).tolist()], max_new_tokens=1)
        _warmup(eng, config, shape, rng)
        ids = [eng.add_request(p,
                               max_new_tokens=shape["interf_max_new"])
               for p in dec_prompts]
        # Seat the decoders (first token out) before interference.
        t_first, t_done, results = {}, {}, {}
        while len(t_first) < len(ids) and eng.has_work():
            done = eng.step()
            now = time.perf_counter()
            results.update(done)
            for r in eng.slot_req:
                if r is not None and r.generated \
                        and r.req_id not in t_first:
                    t_first[r.req_id] = now
            for rid in done:
                t_first.setdefault(rid, now)
                t_done[rid] = now
        fill = []
        while eng.has_work() or (interfere and fill
                                 and any(i not in results for i in ids)):
            if interfere and len(eng.waiting) < 2 \
                    and any(i not in results for i in ids):
                # Keep a prefill backlog alive for the whole window.
                for _ in range(2):
                    fill.append(eng.add_request(
                        rng.integers(1, config.vocab_size,
                                     shape["interf_prompt_len"]).tolist(),
                        max_new_tokens=1))
            done = eng.step()
            now = time.perf_counter()
            results.update(done)
            for rid in done:
                t_done[rid] = now
            if all(i in results for i in ids):
                break
        tpot = [(t_done[i] - t_first[i]) / (len(results[i]) - 1)
                for i in ids if len(results.get(i, [])) > 1]
        return _pct(tpot, 99) * 1e3, len(fill)

    base_p99, _ = _measure(False)
    loaded_p99, n_fill = _measure(True)
    return {
        "decoders": n_dec,
        "decode_tpot_p99_ms_alone": round(base_p99, 3),
        "decode_tpot_p99_ms_with_prefill": round(loaded_p99, 3),
        "tpot_ratio": round(loaded_p99 / base_p99, 3),
        "prefill_requests_injected": n_fill,
        "prefill_budget": shape["prefill_budget"],
    }


def run_tracing_overhead(config, shape):
    """Paired tracing-on/off rows: the identical workload driven twice
    on fresh engines, once with a request-journey trace context on
    every request (phase spans recorded into the in-process ring) and
    once without.  Best-of-3 per arm to shave scheduler noise; the
    journey instrumentation is a handful of ring appends per request
    plus a sampled per-step snapshot, so the tok/s delta must stay
    small (the committed threshold is pinned by tests)."""
    from ray_tpu.util import tracing

    rng = np.random.default_rng(4)
    n = max(64, 4 * shape["max_batch"])
    prompts = [rng.integers(1, config.vocab_size,
                            shape["prompt_len"]).tolist()
               for _ in range(n)]

    def _arm(traced):
        eng = _mk_engine(config, shape)
        _warmup(eng, config, shape, rng)
        tracing.clear_spans()
        t0 = time.perf_counter()
        ids = []
        for i, p in enumerate(prompts):
            ctx = (f"{i:016x}", f"{i:016x}") if traced else None
            ids.append(eng.add_request(
                p, max_new_tokens=shape["max_new"], trace_ctx=ctx))
        results, _, _, _ = _drive(eng, ids, {})
        dt = time.perf_counter() - t0
        toks = sum(len(results[i]) for i in ids)
        spans = len(tracing.get_spans()) + tracing.dropped_span_count()
        tracing.clear_spans()
        return toks / dt, spans

    tps_on, tps_off, spans_on = 0.0, 0.0, 0
    for _ in range(3):  # alternate arms so drift hits both equally
        on, n_spans = _arm(True)
        off, _ = _arm(False)
        tps_on, tps_off = max(tps_on, on), max(tps_off, off)
        spans_on = max(spans_on, n_spans)
    overhead = (tps_off - tps_on) / tps_off * 100.0 if tps_off else 0.0
    print(f"tracing overhead: on={tps_on:.1f} off={tps_off:.1f} tok/s "
          f"({overhead:+.2f}%)", file=sys.stderr)
    return {
        "requests_per_arm": n,
        "tokens_per_sec_traced": round(tps_on, 1),
        "tokens_per_sec_untraced": round(tps_off, 1),
        "overhead_pct": round(overhead, 3),
        "spans_per_run": spans_on,
    }


def run_disaggregated(config, shape):
    """Paired mixed-vs-disaggregated rows for the prefill/decode split.

    Interference pair: the same long decoders + continuous prefill
    stream measured twice — MIXED (one engine runs both, prefill
    admission waves interleave with the decoders' steps) and
    DISAGGREGATED (the prefill stream runs on a separate engine, as a
    prefill-role replica would).  Decode TPOT is measured on the decode
    engine's BUSY clock (time inside its own step() calls), so the
    prefill engine's host time doesn't bleed into the disaggregated row
    — on a real deployment the pools are separate chips.

    Prefix pair: N requests sharing a system prompt flow
    prefill_only -> KV handoff -> decode_from across two LLMServer
    instances; the decode side's cross-replica prefix-cache hit rate
    and token-exactness vs a single mixed server are the row."""
    rng = np.random.default_rng(3)
    n_dec = max(2, shape["max_batch"] // 2)
    dec_prompts = [rng.integers(1, config.vocab_size,
                                shape["prompt_len"]).tolist()
                   for _ in range(n_dec)]

    def _measure(mode):
        eng_d = _mk_engine(config, shape)
        eng_p = eng_d if mode != "disaggregated" \
            else _mk_engine(config, shape)
        for eng in {id(eng_d): eng_d, id(eng_p): eng_p}.values():
            eng.generate(dec_prompts,
                         max_new_tokens=shape["interf_max_new"])
            eng.generate([rng.integers(
                1, config.vocab_size,
                shape["interf_prompt_len"]).tolist()], max_new_tokens=4)
            _warmup(eng, config, shape, rng)
        ids = [eng_d.add_request(p,
                                 max_new_tokens=shape["interf_max_new"])
               for p in dec_prompts]
        busy = 0.0  # decode engine's attributed clock
        t_first, t_done, results = {}, {}, {}
        fill = []
        while any(i not in results for i in ids):
            if mode != "alone" and len(eng_p.waiting) < 2:
                for _ in range(2):
                    fill.append(eng_p.add_request(
                        rng.integers(
                            1, config.vocab_size,
                            shape["interf_prompt_len"]).tolist(),
                        max_new_tokens=4))
            t0 = time.perf_counter()
            done = eng_d.step()
            busy += time.perf_counter() - t0
            results.update(done)
            for r in eng_d.slot_req:
                if r is not None and r.generated \
                        and r.req_id not in t_first:
                    t_first[r.req_id] = busy
            for rid in done:
                t_first.setdefault(rid, busy)
                t_done[rid] = busy
            if eng_p is not eng_d and eng_p.has_work():
                eng_p.step()  # prefill pool: not on the decode clock
        while eng_p.has_work():
            eng_p.step()  # drain stragglers (not measured)
        tpot = [(t_done[i] - t_first[i]) / (len(results[i]) - 1)
                for i in ids if len(results.get(i, [])) > 1]
        return _pct(tpot, 99) * 1e3, len(fill)

    alone_p99, _ = _measure("alone")
    rows = {}
    for mode in ("mixed", "disaggregated"):
        p99, n_fill = _measure(mode)
        rows[mode] = {
            "decode_tpot_p99_ms_alone": round(alone_p99, 3),
            "decode_tpot_p99_ms_with_prefill": round(p99, 3),
            "tpot_ratio": round(p99 / alone_p99, 3),
            "prefill_requests_injected": n_fill,
        }

    # -- cross-replica prefix pair -------------------------------------
    from ray_tpu.serve import llm as llm_mod

    LLMServer = llm_mod.LLMServer.func_or_class
    kw = dict(config=config, page_size=shape["page_size"],
              num_pages=shape["num_pages"], max_batch=shape["max_batch"],
              multi_step=shape["multi_step"],
              prefill_budget=shape["prefill_budget"])
    pre, dec, ref = LLMServer(**kw), LLMServer(**kw), LLMServer(**kw)
    sys_prompt = rng.integers(
        1, config.vocab_size, 2 * shape["page_size"]).tolist()
    n_req, max_new, matched = 6, 2 * shape["multi_step"], 0
    for _ in range(n_req):
        prompt = sys_prompt + rng.integers(
            1, config.vocab_size, 3).tolist()
        kv = pre.prefill_only(prompt, max_new_tokens=max_new)
        got = dec.decode_from(prompt, kv, max_new_tokens=max_new)
        want = ref._submit_and_wait([prompt], max_new, 0.0)[0]
        matched += int(got == want)
    hits = dec.engine.prefix_cache.hits
    rows["cross_replica_prefix"] = {
        "requests": n_req,
        "kv_handoffs": dec.engine.kv_imports,
        "handoff_fallbacks": dec.handoff_fallbacks,
        "prefix_hits": hits,
        "prefix_hit_rate": hits / n_req,
        "tokens_saved": dec.engine.prefix_cache.tokens_saved,
        "tokens_match_mixed_reference": matched == n_req,
    }
    print(f"disagg: tpot_ratio mixed={rows['mixed']['tpot_ratio']} "
          f"disaggregated={rows['disaggregated']['tpot_ratio']} "
          f"prefix_hit_rate={hits / n_req:.3f}", file=sys.stderr)
    return rows


def main():
    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.util import compile_cache, device_stats

    compile_cache.enable()
    rehearse = "--rehearse" in sys.argv[1:]
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not (on_tpu or rehearse):
        print(f"bench_serve.py measures a TPU; found "
              f"{devices[0].platform!r}. Use --rehearse to exercise "
              "the code path at tiny shapes off-chip.", file=sys.stderr)
        return 2
    if on_tpu and not rehearse:
        # An unknown device kind raises: no peak rate is assumed.
        hbm_gb_s = device_stats.peak_specs_for(devices[0].device_kind)[0]
        # The 1.1B GQA 4:1 model + page_size=128 of bench_decode.py;
        # 1024 clients = 8x its request count at the same
        # per-request shape as its 128+128 headline row.
        config = tfm.TransformerConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=22, num_heads=16, num_kv_heads=4,
            max_seq_len=2048, remat=False)
        shape = dict(n_clients=1024, prompt_len=128, max_new=128,
                     page_size=128, num_pages=320, max_batch=128,
                     multi_step=32, prefill_budget=4096,
                     interf_prompt_len=512, interf_max_new=256,
                     burst_deadline_s=1.0)
    else:
        hbm_gb_s = None
        config = tfm.TransformerConfig.tiny()
        shape = dict(n_clients=1024, prompt_len=8, max_new=8,
                     page_size=4, num_pages=64, max_batch=8,
                     multi_step=4, prefill_budget=16,
                     interf_prompt_len=16, interf_max_new=64,
                     burst_deadline_s=0.05)

    sustained = run_sustained(config, shape, hbm_gb_s)
    burst = run_burst_shed(config, shape)
    interference = run_prefill_interference(config, shape)
    tracing_overhead = run_tracing_overhead(config, shape)
    disagg = run_disaggregated(config, shape) \
        if "--disagg" in sys.argv[1:] else None
    head = {"rehearsal": True} if hbm_gb_s is None else {
        "metric": "serve_tokens_per_sec",
        "value": sustained["tokens_per_sec"],
        "unit": "tokens/s",
        "concurrent_clients": sustained["concurrent_clients"],
        "roofline_fraction": sustained["roofline_fraction"],
        "roofline_note": ("whole-run rate (queueing + prefill + decode "
                          "+ drain) vs HBM_BW / (weight_bytes + avg "
                          "live KV bytes) x batch — bench_decode's "
                          "roofline, amortized over 8x its requests"),
    }
    print(json.dumps({
        **head,
        "sustained_load": sustained,
        "burst_shed": burst,
        "prefill_interference": interference,
        "tracing_overhead": tracing_overhead,
        **({"disaggregated": disagg} if disagg is not None else {}),
        "model_params": tfm.num_params(config),
        "device": devices[0].device_kind,
        "on_tpu": on_tpu,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
