"""The program's own timeline: spans on the profiler's clock, JAX's compile
events as spans and totals, the train step's programs in
`compile_counts()`, the start-up timeline `JaxTrainer.fit` writes, and the
evidence spans round the serve lock.  All on the CPU."""

import glob
import json
import os
import statistics
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from ray_tpu.util import device_stats, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.disable_tracing()
    tracing.clear_spans()
    yield
    tracing.disable_tracing()
    tracing.clear_spans()


def _host_events(xplane):
    """[(name, start_ns, duration_ns, stats)] of the host planes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def capture():
    """One real jax.profiler capture of a tiny jit: two forced
    trace_span()s (one with attributes), one the ring does not take, and
    one span recorded after the fact."""
    import jax
    import jax.numpy as jnp

    tracing.disable_tracing()
    tracing.clear_spans()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    d = tempfile.mkdtemp(prefix="profile-")
    jax.profiler.start_trace(d)
    try:
        for i in range(2):
            with tracing.trace_span("unit.step", {"step": i, "blob": [1]},
                                    force=True):
                f(jnp.ones(8)).block_until_ready()
            time.sleep(0.01)
        with tracing.trace_span("unit.quiet"):
            f(jnp.ones(8)).block_until_ready()
        t = time.time()
        tracing.record_span("unit.after", t - 0.5, t - 0.25, force=True)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files
    return {"xplane": files[0], "spans": tracing.get_spans(),
            "events": _host_events(files[0])}


def test_profile_holds_the_span_with_its_epoch(capture):
    evs = [e for e in capture["events"] if e[0] == "ray_tpu:unit.step"]
    ring = [s for s in capture["spans"] if s["name"] == "unit.step"]
    assert len(evs) == len(ring) == 2
    for (_, _, dur, stats), span in zip(sorted(evs, key=lambda e: e[1]),
                                        ring):
        assert abs(stats["t_epoch"] - span["start"]) < 5e-3
        assert stats["span_id"] == span["span_id"]
        assert "blob" not in stats      # scalars only
        assert abs(dur / 1e9 - (span["end"] - span["start"])) < 5e-3
    assert sorted(e[3]["step"] for e in evs) == [0, 1]


def test_profile_holds_spans_the_ring_does_not(capture):
    """Tracing is off: the profile is what turns a span on."""
    assert [e for e in capture["events"] if e[0] == "ray_tpu:unit.quiet"]
    assert not [s for s in capture["spans"] if s["name"] == "unit.quiet"]


def test_recorded_span_leaves_a_marker(capture):
    (ev,) = [e for e in capture["events"]
             if e[0] == "ray_tpu:recorded:unit.after"]
    (span,) = [s for s in capture["spans"] if s["name"] == "unit.after"]
    assert ev[3]["start"] == pytest.approx(span["start"])
    assert ev[3]["end"] == pytest.approx(span["end"])
    assert ev[3]["t_epoch"] > span["end"]


def test_two_events_agree_on_the_clock_offset(capture):
    import opsdump

    planes = opsdump.read_xplane(capture["xplane"])
    offset, spread, n = opsdump.clock_offset(planes)
    assert n >= 3 and spread < 1e-3
    events = opsdump.xplane_events(planes, offset)
    step = [e for e in events if e["name"] == "ray_tpu:unit.step"]
    ring = [s for s in capture["spans"] if s["name"] == "unit.step"]
    assert abs(min(e["ts"] for e in step) / 1e6 - ring[0]["start"]) < 5e-3


def test_no_annotation_and_no_jax_import_without_jax():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing, device_stats\n"
        "tracing.enable_tracing()\n"
        "with tracing.trace_span('a', force=True): pass\n"
        "tracing.record_span('b', 1.0, 2.0, force=True)\n"
        "assert tracing._annotation_cls() is None\n"
        "assert device_stats.install_compile_listener() is False\n"
        "assert device_stats.compile_totals()['compiles'] == 0\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n"
        "print(len(tracing.get_spans()))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "2"


def test_idle_trace_span_records_nothing_and_is_cheap():
    import jax  # noqa: F401 — the bridged path is the one that costs

    assert tracing._annotation_cls() is not None
    # Many short rounds, the least of them: beside five other workers a
    # round of 30 ms is pre-empted every time, one of 3 ms not always.
    n = 1_000
    best = float("inf")
    for _ in range(30):
        t = time.perf_counter()
        for i in range(n):
            with tracing.trace_span("idle", {"step": i}):
                pass
        best = min(best, (time.perf_counter() - t) / n)
    assert tracing.get_spans() == []
    assert best < 5e-6, f"{best * 1e6:.2f} us a span"


def test_force_and_start_override():
    with tracing.trace_span("outer", force=True, start=123.0) as outer:
        assert tracing.current_span_name() == "outer"
        with tracing.trace_span("inner", force=True):
            pass
        with tracing.trace_span("unforced"):
            assert tracing.current_span_name() == "unforced"
    assert tracing.current_span_name() is None
    spans = {s["name"]: s for s in tracing.get_spans()}
    assert set(spans) == {"outer", "inner"}
    assert spans["outer"]["start"] == 123.0
    assert spans["inner"]["parent_id"] == outer


def test_process_start_time_is_before_now_and_steady():
    a, b = tracing.process_start_time(), tracing.process_start_time()
    assert a is not None and abs(a - b) < 0.05
    assert 0 < time.time() - a < 24 * 3600


def test_nested_trace_events_count_once():
    """An inner jit's trace is reported, then the outer one's that
    holds it: the total is the union."""
    device_stats._thread.intervals = []
    assert device_stats._outer_seconds(0.2, 10.5) == pytest.approx(0.2)
    assert device_stats._outer_seconds(0.1, 10.8) == pytest.approx(0.1)
    # [10.0, 11.0] holds both of the above
    assert device_stats._outer_seconds(1.0, 11.0) == pytest.approx(0.7)
    # a later sibling holds none
    assert device_stats._outer_seconds(0.5, 11.6) == pytest.approx(0.5)


def _compile_spans():
    return [s for s in tracing.get_spans() if s["name"] == "xla.compile"]


def test_listener_counts_a_new_shape_and_not_a_repeat():
    import jax
    import jax.numpy as jnp

    assert device_stats.install_compile_listener()
    f = jax.jit(lambda x: jnp.tanh(x) @ x.T)
    x4, x8 = jnp.ones((4, 4)), jnp.ones((8, 8))     # compile their own
    before = device_stats.compile_totals()
    with tracing.trace_span("unit.program", force=True):
        f(x4).block_until_ready()
    mid = device_stats.compile_totals()
    assert mid["compiles"] == before["compiles"] + 1
    assert mid["compile_s"] > before["compile_s"]
    assert mid["trace_lower_s"] > before["trace_lower_s"]
    rows = [s["attributes"] for s in _compile_spans()]
    assert {"backend_compile"} <= {r["event"] for r in rows}
    backend = [r for r in rows if r["event"] == "backend_compile"]
    assert backend[-1]["program"] == "unit.program"
    assert backend[-1]["cache_hit"] is False and backend[-1]["seconds"] > 0
    n_rows = len(rows)
    f(x4).block_until_ready()                        # a repeat
    assert device_stats.compile_totals()["compiles"] == mid["compiles"]
    assert len(_compile_spans()) == n_rows
    f(x8).block_until_ready()                        # a new shape
    assert device_stats.compile_totals()["compiles"] == mid["compiles"] + 1


def test_listener_sees_a_persistent_cache_hit(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    assert device_stats.install_compile_listener()
    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        cc.reset_cache()

        def g(x):
            return jnp.cos(x) * 3.0 + jnp.sin(x) @ x

        x = jnp.ones((16, 16))
        before = device_stats.compile_totals()
        jax.jit(g)(x).block_until_ready()
        cold = device_stats.compile_totals()
        assert cold["cache_misses"] == before["cache_misses"] + 1
        assert cold["cache_hits"] == before["cache_hits"]
        jax.clear_caches()
        jax.jit(g)(x).block_until_ready()
        warm = device_stats.compile_totals()
        assert warm["cache_hits"] == cold["cache_hits"] + 1
        assert warm["cache_misses"] == cold["cache_misses"]
        assert warm["compiles"] == cold["compiles"] + 1
        assert warm["cache_retrieval_s"] > cold["cache_retrieval_s"]
        hit = [s["attributes"] for s in _compile_spans()
               if s["attributes"]["cache_hit"]]
        assert {r["event"] for r in hit} == {"cache_retrieval",
                                             "backend_compile"}
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


TINY = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=1, num_heads=2, num_kv_heads=2, max_seq_len=16)


def _tiny_train_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep

    mesh = build_mesh(axes={"data": 1}, devices=jax.devices()[:1])
    return (ShardedTrainStep(tfm.TransformerConfig(**TINY), mesh),
            {"tokens": jnp.zeros((2, 17), jnp.int32)})


def test_train_step_programs_are_in_compile_counts():
    import jax

    device_stats.reset()
    ts, batch = _tiny_train_step()
    state = ts.init(jax.random.key(0))
    ts.eval_step(state["params"], batch)
    for _ in range(3):
        state, metrics = ts.step(state, batch)
    float(metrics["loss"])
    counts = device_stats.compile_counts()
    assert {k: counts[k]["count"] for k in
            ("train.init", "train.step", "train.eval")} == {
        "train.init": 1, "train.step": 1, "train.eval": 1}
    # tracing is off: only each program's FIRST call reached the ring
    names = [s["name"] for s in tracing.get_spans()
             if s["name"].startswith("train.")]
    assert sorted(names) == ["train.eval", "train.init", "train.step"]
    (first,) = [s for s in tracing.get_spans() if s["name"] == "train.step"]
    # .. and carries what the first step decided of remat (PR 40): on
    # the CPU, which reports no limit, out and lse are kept
    kept = first["attributes"]
    assert kept == {"step": 1, "remat": "kept:attn_out,attn_lse",
                    "remat_program_bytes": kept["remat_program_bytes"],
                    "remat_beside_bytes": 0, "bytes_limit": None}
    assert kept["remat_program_bytes"] > 0
    programs = {s["attributes"]["program"] for s in _compile_spans()
                if s["attributes"]["event"] == "backend_compile"}
    assert {"train.init", "train.step", "train.eval"} <= programs
    tracing.enable_tracing()
    ts.step(state, batch)
    assert [s["attributes"] for s in tracing.get_spans()
            if s["name"] == "train.step"][-1] == {"step": 4}


@pytest.fixture(scope="module")
def fit_result():
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):       # a closure: pickled by value for the worker
        import jax
        import jax.numpy as jnp

        from ray_tpu import train
        from ray_tpu.models import transformer as tfm
        from ray_tpu.parallel.mesh import build_mesh
        from ray_tpu.train.train_state import ShardedTrainStep

        ts = ShardedTrainStep(
            tfm.TransformerConfig(**config["model"]),
            build_mesh(axes={"data": 1}, devices=jax.devices()[:1]))
        batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
        state = ts.init(jax.random.key(0))
        for _ in range(config["steps"]):
            state, metrics = ts.step(state, batch)
            train.report({"loss": float(metrics["loss"])})

    ray_tpu.init(num_cpus=2)
    try:
        return JaxTrainer(
            loop, train_loop_config={"steps": 3, "model": TINY},
            scaling_config=ScalingConfig(num_workers=1),
            # <out>/train/<cell>: where the benchmark's driver puts a run
            run_config=RunConfig(storage_path=os.path.join(tempfile.mkdtemp(
                prefix="timeline-"), "train"), name="run"),
        ).fit()
    finally:
        ray_tpu.shutdown()


DRIVER_SPANS = {"startup.process", "startup.runtime", "startup.head",
                "startup.node_manager", "startup.worker_template",
                "startup.fit", "startup.placement_group",
                "startup.worker_spawn", "startup.backend_on_start",
                "startup.start_training"}
WORKER_SPANS = {"startup.worker_boot", "startup.import_jax",
                "startup.device_client", "startup.loop_entered",
                "train.init", "train.step", "train.report"}


def test_fit_leaves_a_timeline_with_every_phase(fit_result):
    with open(os.path.join(fit_result.path, "timeline.json")) as f:
        doc = json.load(f)
    assert doc == fit_result.timeline
    by_worker = {}
    for s in doc["spans"]:
        by_worker.setdefault(s["worker"], set()).add(s["name"])
        assert s["pid"] > 0
    assert by_worker["driver"] == DRIVER_SPANS
    # train.report: every report of a run this short is in the ring only
    # when tracing is on, so it is not among the forced ones
    assert by_worker["rank0"] == WORKER_SPANS - {"train.report"}
    assert len({s["pid"] for s in doc["spans"]}) == 2
    totals = doc["compile_totals"]
    assert set(totals) == {"driver", "rank0"}   # process totals, each
    assert totals["rank0"]["compiles"] >= 2
    assert totals["rank0"]["compile_s"] > 0
    assert doc["compiles"] and all(
        s["name"] == "xla.compile" and s["worker"] == "rank0"
        for s in doc["compiles"])


def test_an_untraced_fit_writes_no_program_report(fit_result):
    """`programs` (the step program's report, PR 39) is a traced run's:
    this fit ran no profile and enabled no tracing, so its worker lowered
    nothing after the loop and the file has no such key."""
    assert "programs" not in fit_result.timeline
    with open(os.path.join(fit_result.path, "timeline.json")) as f:
        assert "programs" not in json.load(f)


def test_timeline_phases_nest_and_tile(fit_result):
    spans = fit_result.timeline["spans"]
    by_id = {s["span_id"]: s for s in spans}
    first = {}
    for s in sorted(spans, key=lambda s: s["start"]):
        first.setdefault((s["worker"], s["name"]), s)
    eps = 0.02      # the OS's process start time comes in 10 ms steps

    def parent_of(name):
        return by_id[first[("driver", name)]["parent_id"]]["name"]

    assert first[("driver", "startup.runtime")]["parent_id"] is None
    for child in ("startup.head", "startup.node_manager"):
        assert parent_of(child) == "startup.runtime"
    assert parent_of("startup.worker_template") == "startup.head"
    for child in ("startup.placement_group", "startup.worker_spawn",
                  "startup.backend_on_start", "startup.start_training"):
        assert parent_of(child) == "startup.fit"
    kids = {}
    for s in spans:
        parent = by_id.get(s["parent_id"])
        if parent is not None:
            assert parent["start"] - eps <= s["start"], s["name"]
            assert s["end"] <= parent["end"] + eps, s["name"]
            kids.setdefault(parent["span_id"], []).append(s)
    for group in kids.values():
        group.sort(key=lambda s: s["start"])
        for a, b in zip(group, group[1:]):
            assert b["start"] >= a["end"] - 1e-6, (a["name"], b["name"])
    # one process after the other, on one clock
    d, w = (lambda n: first[("driver", n)]), (lambda n: first[("rank0", n)])
    assert d("startup.process")["end"] <= d("startup.runtime")["start"] + eps
    assert d("startup.runtime")["end"] <= d("startup.fit")["start"] + eps
    spawn, boot = d("startup.worker_spawn"), w("startup.worker_boot")
    assert spawn["start"] - eps <= boot["start"] and boot["end"] <= spawn["end"]
    backend = d("startup.backend_on_start")
    for name in ("startup.import_jax", "startup.device_client"):
        assert backend["start"] <= w(name)["start"]
        assert w(name)["end"] <= backend["end"]
    entered = w("startup.loop_entered")
    assert entered["start"] == entered["end"]
    assert d("startup.start_training")["start"] <= entered["start"]
    assert entered["start"] <= w("train.init")["start"] \
        <= w("train.step")["start"]
    assert w("train.step")["attributes"]["step"] == 1


def test_a_second_fit_keeps_the_first_fits_phases_out(fit_result):
    """The driver's ring still holds the first run's startup.fit: the
    second run's timeline has its own only (and the runtime's)."""
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ray_tpu.init(num_cpus=2)
    try:
        res = JaxTrainer(
            lambda: train.report({"x": 1}),
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(storage_path=tempfile.mkdtemp(
                prefix="timeline-"), name="second"),
        ).fit()
    finally:
        ray_tpu.shutdown()
    names = [s["name"] for s in res.timeline["spans"]
             if s["worker"] == "driver"]
    assert names.count("startup.fit") == 1
    assert names.count("startup.worker_spawn") == 1
    assert "startup.runtime" in names


def test_a_stale_timeline_is_refused_by_a_reader(fit_result, monkeypatch):
    from benchmark import harness, timeline_lib

    monkeypatch.setattr(harness, "OUT_DIR", os.path.dirname(
        os.path.dirname(fit_result.path)))
    cell = {"cell": {"name": "run"}}
    began = timeline_lib.spans(fit_result.timeline, "startup.process",
                               "driver")[0]["start"]
    main = sys.modules["__main__"]
    monkeypatch.setattr(main, "T_PROCESS_START", began + 0.5, raising=False)
    assert timeline_lib.load(cell) == fit_result.timeline
    # the file is of a run that began a minute before this process
    monkeypatch.setattr(main, "T_PROCESS_START", began + 60.0)
    assert timeline_lib.load(cell) is None
    monkeypatch.delattr(main, "T_PROCESS_START")
    assert timeline_lib.load(cell) is None


def test_lock_wait_spans_add_up_to_the_time_handlers_were_held_off():
    """The engine thread holds the server's lock through engine.step():
    handlers that enqueue meanwhile wait, and `serve.lock_wait` says for
    how long."""
    from ray_tpu.serve import llm as llm_mod

    srv = llm_mod.LLMServer.func_or_class(page_size=4, num_pages=64,
                                           max_batch=4)
    real_step, real_add = srv.engine.step, srv.engine.add_request
    got_lock = {}

    def slow_step():
        time.sleep(0.15)            # the device's time, lock held
        return real_step()

    def stamped_add(*a, **kw):
        got_lock[threading.get_ident()] = time.time()
        return real_add(*a, **kw)

    srv.engine.step, srv.engine.add_request = slow_step, stamped_add
    tracing.enable_tracing()
    asked = {}

    def handler(prompt):
        asked[threading.get_ident()] = time.time()
        srv.generate(prompt, max_new_tokens=2)

    threads = [threading.Thread(target=handler, args=([1, 2, 3 + i],))
               for i in range(3)]
    threads[0].start()
    time.sleep(0.05)                # the engine is inside its first step
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    srv._stopped = True
    held_off = sum(got_lock[i] - asked[i] for i in asked)
    waits = [s for s in tracing.get_spans() if s["name"] == "serve.lock_wait"]
    by_who = {}
    for s in waits:
        who = s["attributes"]["who"]
        by_who[who] = by_who.get(who, 0.0) + s["end"] - s["start"]
    assert held_off > 0.05
    assert by_who["add_request"] == pytest.approx(held_off, rel=0.2)
    assert "engine" in by_who
    steps = [s for s in tracing.get_spans()
             if s["name"] == "serve.engine_step"]
    assert steps and statistics.median(
        s["end"] - s["start"] for s in steps) >= 0.15
