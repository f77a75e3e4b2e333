"""Decode/serving benchmark: tokens/s through LLMEngine.step on TPU
(paged KV cache + continuous batching + chunked multi-step decode).

Run: python scripts/bench_decode.py  (writes one JSON line to stdout).
Off-chip it exits non-zero; --rehearse runs the same code path at a
tiny shape and prints no device metric.

The reference has no comparable in-tree number (its serve LLM tests are
pass/fail wrappers); this establishes the framework's own baseline, per
BASELINE.md 'Missing from reference'.  Two shapes run: the r02
comparison point (128+128) and a longer-generation shape (128+512).

Honesty rules:
  - decode-only throughput excludes engine steps that performed any
    admission/prefill work; the headline roofline fraction is computed
    against the DECODE-ONLY rate (the whole-run rate is also reported).
  - the roofline counts both traffic terms every decode iteration
    reads: full bf16 weights AND the average live KV context:
        iters/s <= HBM_BW / (weight_bytes + avg_kv_bytes_per_iter)
        tokens/s <= iters/s * batch
  - dispatch is CHUNKED (multi_step=32), not one wave-sized dispatch:
    queued requests join the batch at every chunk boundary (<= 32
    tokens of wait), which is what the continuous-batching claim
    requires; tests/test_llm_decoding.py::test_mid_generation_admission
    pins the behavior.
  - per-request latency is recorded: TTFT (add_request -> first token
    available on the host) and TPOT ((last - first)/(n-1)); p50/p99
    across requests.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def run_shape(config, *, n_requests, prompt_len, max_new, page_size,
              num_pages, max_batch, multi_step, hbm_gb_s):
    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm_engine import LLMEngine

    eng = LLMEngine(config, page_size=page_size, num_pages=num_pages,
                    max_batch=max_batch, multi_step=multi_step)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, config.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    # Warmup compiles every program the measured run hits: the packed
    # admission wave, one decode program per pow-2 context-width
    # bucket, AND the dirty-slot merge (a mid-run admission while old
    # slots finish exercises merge_slot_state; steady-state serving
    # never pays compiles, so neither should the measurement).
    warm = [rng.integers(1, config.vocab_size, prompt_len).tolist()
            for _ in range(max_batch)]
    eng.generate(warm, max_new_tokens=max_new)
    stagger = [rng.integers(1, config.vocab_size, prompt_len).tolist()
               for _ in range(2)]
    eng.add_request(stagger[0], max_new_tokens=max_new)
    eng.step()
    eng.add_request(stagger[1], max_new_tokens=8)
    while eng.has_work():
        eng.step()

    t0 = time.perf_counter()
    t_add = {}
    ids = []
    for p in prompts:
        rid = eng.add_request(p, max_new_tokens=max_new)
        t_add[rid] = time.perf_counter()
        ids.append(rid)
    results = {}
    t_first = {}
    t_done = {}
    steps = 0
    decode_wall = 0.0
    decode_tokens = 0
    emitted_prev = 0

    def emitted_now():
        live = sum(len(r.generated) for r in eng.slot_req if r is not None)
        done = sum(len(v) for v in results.values())
        return live + done

    while eng.has_work():
        waiting_before = len(eng.waiting)
        waves_before = (eng.waves_dispatched, eng.prefill_reconciles)
        ts = time.perf_counter()
        done = eng.step()
        te = time.perf_counter()
        steps += 1
        results.update(done)
        now = te
        for rid, toks in done.items():
            t_done[rid] = now
        for r in eng.slot_req:
            if r is not None and r.generated and r.req_id not in t_first:
                t_first[r.req_id] = now
        for rid in done:
            t_first.setdefault(rid, now)
        emitted = emitted_now()
        if (len(eng.waiting) == waiting_before and waiting_before == 0
                and (eng.waves_dispatched,
                     eng.prefill_reconciles) == waves_before):
            # Pure decode step: no admission/prefill work happened —
            # dispatching a wave or waiting on a wave's first tokens
            # both disqualify the step from the decode-only wall.
            decode_wall += te - ts
            decode_tokens += emitted - emitted_prev
        emitted_prev = emitted
    dt = time.perf_counter() - t0
    assert set(ids) <= set(results), "missing results"
    gen_tokens = sum(len(results[i]) for i in ids)

    weight_bytes = 2 * tfm.num_params(config)
    # Average KV bytes read per decode iteration: bf16 K+V over the
    # average live context across the generation window.
    kv_per_token = (2 * config.num_layers * config.num_kv_heads
                    * config.head_dim_ * 2)
    avg_ctx = prompt_len + max_new / 2
    kv_bytes = max_batch * avg_ctx * kv_per_token
    tok_s = gen_tokens / dt
    decode_tok_s = decode_tokens / decode_wall if decode_wall else 0.0
    ttft = [t_first[i] - t_add[i] for i in ids]
    tpot = [(t_done[i] - t_first[i]) / (len(results[i]) - 1)
            for i in ids if len(results[i]) > 1]
    roofline = {}
    if hbm_gb_s is not None:  # None: --rehearse, no device to bound
        roofline_tok_s = hbm_gb_s / (weight_bytes + kv_bytes) * max_batch
        roofline = {
            "decode_only_roofline_fraction": round(
                decode_tok_s / roofline_tok_s, 3),
            "roofline_tokens_per_sec": round(roofline_tok_s, 1),
            "roofline_fraction": round(tok_s / roofline_tok_s, 3)}
    return {
        "decode_only_tokens_per_sec": round(decode_tok_s, 1),
        "tokens_per_sec": round(tok_s, 1),
        **roofline,
        "ttft_p50_s": round(_pct(ttft, 50), 4),
        "ttft_p99_s": round(_pct(ttft, 99), 4),
        "tpot_p50_ms": round(_pct(tpot, 50) * 1e3, 3),
        "tpot_p99_ms": round(_pct(tpot, 99) * 1e3, 3),
        "generated_tokens": gen_tokens,
        "decode_only_tokens": decode_tokens,
        "decode_only_wall_s": round(decode_wall, 2),
        "prefill_tokens": n_requests * prompt_len,
        "wall_s": round(dt, 2),
        "engine_steps": steps,
        "concurrent_requests": n_requests,
        "max_batch": max_batch,
        "multi_step": multi_step,
        "page_size": page_size,
        "seq": f"{prompt_len}+{max_new}",
    }


def main():
    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.util import compile_cache, device_stats

    compile_cache.enable()
    rehearse = "--rehearse" in sys.argv[1:]
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not (on_tpu or rehearse):
        print(f"bench_decode.py measures a TPU; found "
              f"{devices[0].platform!r}. Use --rehearse to exercise "
              "the code path at a tiny shape off-chip.", file=sys.stderr)
        return 2
    if rehearse:
        eng_rows = [run_shape(
            tfm.TransformerConfig.tiny(), hbm_gb_s=None, n_requests=4,
            prompt_len=8, max_new=8, page_size=4, num_pages=64,
            max_batch=4, multi_step=1)]
        # Counts only: a rate off the chip is not a device metric.
        counts = ("generated_tokens", "prefill_tokens", "engine_steps",
                  "concurrent_requests", "seq")
        print(json.dumps({
            "rehearsal": True, "device": devices[0].device_kind,
            "shapes": [{k: r[k] for k in counts} for r in eng_rows]}))
        return 0
    # An unknown device kind raises: no peak rate is assumed.
    hbm_gb_s = device_stats.peak_specs_for(devices[0].device_kind)[0]
    # 1.1B GQA 4:1 (TinyLlama-class): grouped-query attention is
    # the TPU-first shape — 4x the MXU work per KV byte streamed,
    # 4x smaller KV pool, so batch (and the bandwidth roofline's
    # useful output) doubles.  page_size=128: the decode kernel
    # streams one fused-head page per DMA (ops/paged_attention.py),
    # so pages must be big enough that DMAs amortize issue latency.
    config = tfm.TransformerConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=22, num_heads=16, num_kv_heads=4,
        max_seq_len=2048, remat=False)
    # multi_step=32: chunked dispatch — a whole-generation dispatch
    # would maximize throughput but lock queued requests out for
    # the entire wave; 32 bounds the admission wait (one host sync
    # per 32 device iterations).
    shapes = [
        dict(n_requests=128, prompt_len=128, max_new=128,
             page_size=128, num_pages=320, max_batch=128,
             multi_step=32),
        dict(n_requests=64, prompt_len=128, max_new=512,
             page_size=128, num_pages=384, max_batch=64,
             multi_step=32),
    ]

    rows = [run_shape(config, hbm_gb_s=hbm_gb_s, **s) for s in shapes]
    head = rows[0]
    print(json.dumps({
        "metric": "decode_tokens_per_sec",
        "value": head["decode_only_tokens_per_sec"],
        "unit": "tokens/s",
        "roofline_tokens_per_sec": head["roofline_tokens_per_sec"],
        "roofline_fraction": head["decode_only_roofline_fraction"],
        "roofline_note": ("decode-only rate vs HBM_BW / (weight_bytes "
                          "+ avg live KV bytes) x batch — both traffic "
                          "terms every decode iteration reads; steps "
                          "that did admission/prefill are excluded "
                          "from the decode-only wall; whole-run rate "
                          "(incl. prefill + host dispatch latency) "
                          "reported per shape"),
        "shapes": rows,
        "model_params": tfm.num_params(config),
        "device": devices[0].device_kind,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
