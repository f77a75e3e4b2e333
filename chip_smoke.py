"""Chip smoke: the served path and the train step, once, on the chip.

    python chip_smoke.py             # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4   # four chips: fsdp=4 train step vs one chip
    python chip_smoke.py --rehearse  # CPU rehearsal at tiny(); never "ok": true

Serve: ray_tpu.init() -> serve.run(LLMServer, num_tpus=1) at the full
width of the 1.1 B GQA model -> requests through the HTTP proxy (client
-> proxy -> router -> handle -> replica -> engine).  Train: JaxTrainer
takes 3 ShardedTrainStep steps of a 0.9 B dense model (TRAIN_MODEL).
The phases run one after the other, each torn down before the next: a
chip belongs to one process at a time.  This driver never initialises a
JAX backend; the device is reported by the worker that holds it.

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Anything else on that line, or a non-zero exit, is a failure.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import sys
import threading
import time
from urllib.parse import urlparse

SERVE_MODEL = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                   num_layers=22, num_heads=16, num_kv_heads=4,
                   max_seq_len=2048, remat=False)
SERVE_ENGINE = dict(page_size=128, num_pages=320, max_batch=128,
                    multi_step=32)
TRAIN_MODEL = dict(vocab_size=32000, hidden_size=1792, intermediate_size=7168,
                   num_layers=16, num_heads=14, num_kv_heads=14,
                   max_seq_len=2048, remat_policy="full", fused_ce=True)
# Batch 6: at batch 8 the step program
# needs 15.95 GiB (AOT memory_analysis for v5e) against 15.75 GiB of HBM.
# The four-chip comparison needs a batch that fsdp=4 divides.
TRAIN_BATCH = {1: 6, 4: 4}
TRAIN_SEQ, TRAIN_STEPS = 2048, 3

# --rehearse: same control flow at test size, kernels interpreted.
TINY_SERVE_MODEL = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=512, remat=False)
TINY_SERVE_ENGINE = dict(page_size=16, num_pages=128, max_batch=16,
                         multi_step=4)
TINY_TRAIN_MODEL = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_layers=2, num_heads=4, num_kv_heads=4,
                        max_seq_len=256, remat_policy="full", fused_ce=True)


class SmokeFailure(Exception):
    pass


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def cache_entries() -> int:
    from ray_tpu.util import compile_cache

    try:
        return len(os.listdir(compile_cache.cache_dir()))
    except OSError:
        return 0


def first_response_vs_last_run(seconds: float) -> dict:
    """Seconds to the first served response (deploy + compiles + one
    wave), against the previous run that shared this compile cache."""
    from ray_tpu.util import compile_cache

    path = os.path.join(compile_cache.cache_dir(), "chip_smoke_last.json")
    out = {"seconds_to_first_response": seconds}
    try:
        with open(path) as f:
            prev = json.load(f)["seconds_to_first_response"]
        out.update(previous_run=prev, faster_than_previous_run=seconds < prev)
    except (OSError, ValueError, KeyError):
        out["previous_run"] = None
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"seconds_to_first_response": seconds}, f)
    return out


def check_kernels(kernels: dict, must_take: tuple) -> None:
    """Every op the main path needs took its Pallas kernel, and nothing
    ran interpreted or gave way to the XLA formulation."""
    for op in must_take:
        check(kernels.get(op, {}).get("pallas", 0) > 0,
              f"{op} never took its Pallas kernel: {kernels}")
        check(set(kernels[op]) == {"pallas"},
              f"{op} also ran off the kernel: {kernels[op]}")
    for op, paths in kernels.items():
        check("interpret" not in paths, f"{op} ran interpreted: {paths}")


# ---------------------------------------------------------------------------
# serve phase


def _post(base: str, path: str, body: dict, stream: bool = False,
          timeout: float = 900.0):
    """One HTTP request through the proxy.  Returns (tokens, seconds to
    the first token, seconds to the end)."""
    u = urlparse(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    headers = {"Content-Type": "application/json"}
    if stream:
        headers["X-Serve-Stream"] = "1"
    t0 = time.perf_counter()
    conn.request("POST", path, body=json.dumps(body).encode(),
                 headers=headers)
    resp = conn.getresponse()
    try:
        if not stream:
            data = resp.read()
            check(resp.status == 200, f"HTTP {resp.status}: {data[:500]!r}")
            dt = time.perf_counter() - t0
            return json.loads(data), dt, dt
        check(resp.status == 200, f"HTTP {resp.status} on stream")
        toks, ttft = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.strip():
                continue
            doc = json.loads(line)
            check("token" in doc, f"stream item without a token: {doc}")
            if ttft is None:
                ttft = time.perf_counter() - t0
            toks.append(doc["token"])
        return toks, ttft, time.perf_counter() - t0
    finally:
        conn.close()


def serve_phase(args, rehearse: bool) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.serve.llm import LLMServer

    import numpy as np

    model = TINY_SERVE_MODEL if rehearse else SERVE_MODEL
    engine = TINY_SERVE_ENGINE if rehearse else SERVE_ENGINE
    page = engine["page_size"]
    prompt_len, new_tokens, n_conc = page, page // 2, 8
    stream_len, stream_new = 2 * page, page // 4

    @deployment(name="chat", max_ongoing_requests=32)
    class Chat:
        """HTTP ingress: JSON in, tokens out (streamed when asked)."""

        def __init__(self, llm):
            self.llm = llm

        def __call__(self, request):
            body = request.json()
            prompt, n = body["prompt"], int(body["max_new_tokens"])
            if body.get("stream"):
                return ({"token": t} for t in self.llm.options(
                    stream=True, method_name="generate_stream").remote(
                        prompt, n))
            return self.llm.generate.remote(prompt, n).result(timeout_s=900)

    entries0 = cache_entries()
    t0 = time.perf_counter()
    llm = LLMServer.options(
        ray_actor_options={"num_tpus": 1}, max_ongoing_requests=32,
        health_check_timeout_s=900.0,
    ).bind(config=TransformerConfig(**model), seed=args.seed, **engine)
    serve.start()
    serve.run(Chat.bind(llm), name="smoke", route_prefix="/smoke",
              blocking_timeout_s=900.0)
    base = serve.proxy_address()
    t_deploy = time.perf_counter() - t0
    say("serve.deploy", seconds=t_deploy, proxy=base, model=model,
        engine=engine)

    rng = np.random.default_rng(args.seed)
    vocab = model["vocab_size"]
    prompts = [rng.integers(1, vocab, prompt_len).tolist()
               for _ in range(n_conc)]
    results: list = [None] * n_conc
    errors: list = []

    def one(i):
        try:
            results[i] = _post(base, "/smoke", {
                "prompt": prompts[i], "max_new_tokens": new_tokens})
        except Exception as e:  # noqa: BLE001 — re-raised by wave()
            errors.append(e)

    def wave():
        t = time.perf_counter()
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_conc)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]
        return [r[0] for r in results], time.perf_counter() - t

    cold, t_cold = wave()     # compiles prefill + decode programs
    say("serve.first_response",
        **first_response_vs_last_run(time.perf_counter() - t0))
    for toks in cold:
        check(len(toks) == new_tokens, f"got {len(toks)} tokens")
        check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
              "token outside the vocabulary")
    warm, t_warm = wave()     # same prompts, programs compiled
    say("serve.concurrent", requests=n_conc, prompt_tokens=prompt_len,
        new_tokens=new_tokens, cold_seconds=t_cold, warm_seconds=t_warm,
        warm_tokens_per_second=n_conc * new_tokens / t_warm,
        first_request_tokens=cold[0][:16])

    # Same prompt, same path, twice: both runs find the prompt's page in
    # the prefix cache, so they run the same programs on the same data
    # and must agree token for token.  (The cold run above took the
    # uncached prefill program; bf16 may break ties differently there.)
    again1, _, _ = _post(base, "/smoke", {"prompt": prompts[0],
                                          "max_new_tokens": new_tokens})
    again2, _, t_one = _post(base, "/smoke", {"prompt": prompts[0],
                                              "max_new_tokens": new_tokens})
    check(again1 == again2, "the same prompt gave different tokens twice: "
          f"{again1} vs {again2}")
    say("serve.repeat", same_tokens_twice=True, seconds=t_one,
        equals_uncached_run=again1 == cold[0])

    sprompt = rng.integers(1, vocab, stream_len).tolist()
    streamed, ttft, t_stream = _post(
        base, "/smoke", {"prompt": sprompt, "max_new_tokens": stream_new,
                         "stream": True}, stream=True)
    check(len(streamed) == stream_new,
          f"stream gave {len(streamed)} tokens, wanted {stream_new}")
    check(all(0 <= t < vocab for t in streamed), "streamed token off vocab")
    say("serve.stream", prompt_tokens=stream_len, new_tokens=stream_new,
        seconds_to_first_token=ttft, seconds=t_stream, tokens=streamed[:16])

    stats = serve.get_deployment_handle(
        "llm_server", app_name="smoke").stats.remote().result(timeout_s=60)
    device, kernels = stats["device"], stats["kernels"]
    entries1 = cache_entries()
    say("serve.replica", device=device, kernels=kernels,
        hbm_peak_bytes=stats["hbm_peak_bytes"],
        completed=stats["num_completed"], shed=stats["num_shed"],
        compile_cache={"entries_before": entries0, "entries_after": entries1,
                       "hit": entries0 > 0 and entries1 == entries0})
    check(stats["num_shed"] == 0, "the engine shed requests")
    if not rehearse:
        check(device["backend"] == "tpu",
              f"the replica serves on {device['backend']!r}, not on the TPU")
        check_kernels(kernels, ("paged_attention", "write_token_rows",
                                "flash_attention"))
    serve.shutdown()
    return {"platform": device["backend"], "kind": device["device_kind"],
            "count": device["num_devices"]}


# ---------------------------------------------------------------------------
# train phase


def train_loop(cfg):
    """Runs ON the train worker, which holds the chip(s)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer
    from ray_tpu.util import device_stats

    all_devices = jax.devices()
    config = tfm.TransformerConfig(**cfg["model"])
    rng = np.random.default_rng(cfg["seed"])
    tokens = rng.integers(0, config.vocab_size,
                          (cfg["batch"], cfg["seq"] + 1)).astype(np.int32)
    for n in cfg["meshes"]:
        devices = all_devices[:n]
        mesh = build_mesh(axes={"fsdp": n}, devices=devices)
        ts = ShardedTrainStep(
            config, mesh,
            optimizer=default_optimizer(warmup_steps=2, total_steps=1000,
                                        mu_dtype=jnp.bfloat16,
                                        nu_dtype=jnp.bfloat16))
        state = ts.init(jax.random.key(cfg["seed"]))
        wq = state["params"]["blocks"]["wq"]
        shards = wq.addressable_shards
        placement = {
            "shard_devices": sorted({s.device.id for s in shards}),
            "shard_shape": list(shards[0].data.shape),
            "full_shape": list(wq.shape),
        }
        for step in range(cfg["steps"]):
            t0 = time.perf_counter()
            state, metrics = ts.step(state, {"tokens": jnp.asarray(tokens)})
            loss = float(metrics["loss"])
            calls = None
            if step == cfg["steps"] - 1:    # the program that ran, once
                rows = device_stats.program_report("train.step")[
                    "instructions"].values()
                calls = [sum(r[3] == "tpu_custom_call" and f"/{k}/" in r[1]
                             for r in rows)
                         for k in ("flash_fwd", "flash_bwd")]
            train.report({
                "mesh": n, "step": step, "loss": loss,
                "grad_norm": float(metrics["grad_norm"]),
                "seconds": time.perf_counter() - t0,
                "platform": all_devices[0].platform,
                "kind": all_devices[0].device_kind,
                "count": len(all_devices),
                "placement": placement,
                "kernels": dispatch.taken(),
                "flash_calls": calls,
                "hbm_peak_bytes": int((device_stats.memory_stats() or {})
                                      .get("peak_bytes_in_use", 0)),
            })
        del state, metrics, wq, shards


def train_phase(args, rehearse: bool, meshes: list) -> dict:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    chips = max(meshes)
    model = TINY_TRAIN_MODEL if rehearse else TRAIN_MODEL
    seq = 256 if rehearse else TRAIN_SEQ
    entries0 = cache_entries()
    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config={"model": model, "batch": TRAIN_BATCH[chips],
                           "seq": seq,
                           "steps": TRAIN_STEPS, "seed": args.seed,
                           "meshes": meshes},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpu_chips_per_worker=chips),
    ).fit()
    t_fit = time.perf_counter() - t0
    hist = result.metrics_history
    check(len(hist) == TRAIN_STEPS * len(meshes),
          f"{len(hist)} reports for {TRAIN_STEPS * len(meshes)} steps")
    last = hist[-1]
    entries1 = cache_entries()
    by_mesh = {}
    for n in meshes:
        rows = [h for h in hist if h["mesh"] == n]
        losses = [h["loss"] for h in rows]
        check(all(l == l and abs(l) < 1e4 for l in losses),
              f"loss not finite on mesh {n}: {losses}")
        check(all(b <= a + 1e-3 for a, b in zip(losses, losses[1:])),
              f"loss rose on mesh {n}: {losses}")
        by_mesh[n] = rows
        say("train.steps", mesh=n, losses=losses,
            step_seconds=[h["seconds"] for h in rows],
            placement=rows[0]["placement"],
            hbm_peak_bytes=rows[-1]["hbm_peak_bytes"])
    say("train.worker", seconds=t_fit,
        device={"platform": last["platform"], "kind": last["kind"],
                "count": last["count"]},
        kernels=last["kernels"],
        compile_cache={"entries_before": entries0, "entries_after": entries1,
                       "hit": entries0 > 0 and entries1 == entries0})
    check(last["count"] == chips,
          f"the worker sees {last['count']} devices, wanted {chips}")
    if len(meshes) > 1:
        one, four = by_mesh[1], by_mesh[chips]
        place = four[0]["placement"]
        check(len(place["shard_devices"]) == chips,
              f"parameters sit on devices {place['shard_devices']}, "
              f"not on {chips} distinct ones")
        check(place["shard_shape"] != place["full_shape"],
              "every device holds a full copy: parameters are not sharded")
        l1, l4 = one[0]["loss"], four[0]["loss"]
        tol = 5e-3 * max(1.0, abs(l1))  # bf16 compute, different reductions
        check(abs(l1 - l4) <= tol,
              f"first-step loss differs: {l1} on one chip, {l4} on {chips}")
        say("train.compare", loss_one_chip=l1, loss_sharded=l4,
            abs_diff=abs(l1 - l4), tolerance=tol,
            shard_devices=place["shard_devices"])
    if not rehearse:
        check(last["platform"] == "tpu",
              f"the train worker runs on {last['platform']!r}, not the TPU")
        check_kernels(last["kernels"], ("flash_attention",))
        # What each mesh's first step decided of remat ("full": what fits
        # is kept), in the order of the meshes, and the flash calls of the
        # program that ran: one that had room for the kept out and lse
        # runs each layer's forward kernel once, not twice.
        records = [r for r, times in last["kernels"]["train.remat"].items()
                   for _ in range(times)]
        say("train.remat", records=records,
            flash_calls={n: by_mesh[n][-1]["flash_calls"] for n in meshes})
        for n, record in zip(meshes, records):
            # a program the compiler refused gives no bytes to read: no room
            read = re.search(r"program(\d+)of(\d+),beside(\d+)$", record)
            room = bool(read) and (
                int(read[1]) + int(read[3]) <= int(read[2]))
            fwd, bwd = by_mesh[n][-1]["flash_calls"]
            check(fwd == (bwd if room else 2 * bwd) > 0,
                  f"mesh {n}: {record}, yet {fwd} forward flash calls "
                  f"for {bwd} backward")
    return {"platform": last["platform"], "kind": last["kind"],
            "count": last["count"]}


# ---------------------------------------------------------------------------


def run(args) -> dict:
    rehearse = args.rehearse
    if rehearse:
        # Explicitly a CPU run: workers inherit these.  Never otherwise —
        # a replica would inherit JAX_PLATFORMS=cpu and serve off-chip.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")
    import ray_tpu
    from ray_tpu.core.runtime import get_runtime
    from ray_tpu.util import compile_cache

    ray_tpu.init(num_tpus=args.chips if rehearse else None)
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        check(have >= args.chips,
              f"this host offers {have:g} TPU chips, the run needs "
              f"{args.chips}: a num_tpus actor would wait for ever")
        native = bool(get_runtime().core.store.native)
        say("runtime", tpu_chips=have, native=native,
            compile_cache_dir=compile_cache.cache_dir())
        check(native, "the native object arena did not build; the store "
              "fell back to file-per-object")
        if args.chips == 1:
            device = serve_phase(args, rehearse)
            train_device = train_phase(args, rehearse, [1])
            check(train_device == device,
                  f"serve ran on {device}, train on {train_device}")
        else:
            device = train_phase(args, rehearse, [1, args.chips])
    finally:
        ray_tpu.shutdown()
    import jax._src.xla_bridge as xb

    check(not xb.backends_are_initialized(),
          "the driver initialised a JAX backend; it must stay off the chip")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny size; never prints ok:true")
    args = ap.parse_args()

    def on_alarm(signum, frame):
        raise SmokeFailure("time limit: the smoke did not finish in 1150 s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(1150)
    t0 = time.perf_counter()
    try:
        device = run(args)
    except Exception as e:  # noqa: BLE001 — reported; the exit code says so
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}),
              flush=True)
        return 1
    say("done", seconds=time.perf_counter() - t0)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # sys.exit, not os._exit: the runtime's atexit hooks stop the worker
    # template process it started.
    sys.exit(main())
