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


# What the epoch clock's two readings round a span may differ from the
# profiler's clock by: a float of today's epoch steps by a quarter of a
# microsecond.  No limit here rests on how soon a worker gets its core back.
CLOCK_STEP = 1e-5


def test_profile_holds_the_span_with_its_epoch(capture):
    evs = [e for e in capture["events"] if e[0] == "ray_tpu:unit.step"]
    ring = [s for s in capture["spans"] if s["name"] == "unit.step"]
    assert len(evs) == len(ring) == 2
    for (_, _, dur, stats), span in zip(sorted(evs, key=lambda e: e[1]),
                                        ring):
        # ONE reading of the clock is both (`trace_span`'s t0)
        assert stats["t_epoch"] == span["start"]
        assert stats["span_id"] == span["span_id"]
        assert "blob" not in stats      # scalars only
        # the annotation lies INSIDE the span: entered after the span's
        # start was read, left before its end was
        assert dur / 1e9 <= span["end"] - span["start"] + CLOCK_STEP
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
    # an event is placed by the MEDIAN offset, so it lies off its span's
    # own start by no more than the offsets disagree
    assert abs(min(e["ts"] for e in step) / 1e6
               - ring[0]["start"]) <= spread + CLOCK_STEP


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


LOOP_SLEEP_S, SLEEPS_AFTER_STEP = 0.2, 2
DRIVER_HOLD_S, HELD_AT_STEP = 0.4, 4


def _slow_driver():
    """A driver that is slow to take a result: its callback holds the
    poll loop for DRIVER_HOLD_S when step HELD_AT_STEP's result arrives."""
    from ray_tpu.tune.callbacks import Callback

    class SlowDriver(Callback):
        def on_trial_result(self, *, trial, result):
            if result.get("step") == HELD_AT_STEP:
                time.sleep(DRIVER_HOLD_S)

    return SlowDriver()


@pytest.fixture(scope="module")
def fit_result():
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):       # a closure: pickled by value for the worker
        import time

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
        for i in range(1, config["steps"] + 1):
            state, metrics = ts.step(state, batch)
            train.report({"step": i, "loss": float(metrics["loss"])})
            if i == config["sleeps_after_step"]:
                time.sleep(config["sleep_s"])       # the loop's own time

    ray_tpu.init(num_cpus=2)
    try:
        return JaxTrainer(
            loop, train_loop_config={
                "steps": 7, "model": TINY, "sleep_s": LOOP_SLEEP_S,
                "sleeps_after_step": SLEEPS_AFTER_STEP},
            scaling_config=ScalingConfig(num_workers=1),
            # <out>/train/<cell>: where the benchmark's driver puts a run
            run_config=RunConfig(storage_path=os.path.join(tempfile.mkdtemp(
                prefix="timeline-"), "train"), name="run",
                callbacks=[_slow_driver()]),
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


def _ledger(fit_result):
    return fit_result.timeline["steps"]["rank0"]


def test_fit_leaves_one_ledger_row_a_step(fit_result):
    led = _ledger(fit_result)
    rows = led["rows"]
    assert [r[0] for r in rows] == list(range(1, 8))
    assert led["dropped"] == 0 and led["totals"]["steps"] == 7
    entered = [r[1] for r in rows]
    assert all(a < b for a, b in zip(entered, entered[1:]))
    (loop_entered,) = [s for s in fit_result.timeline["spans"]
                       if s["name"] == "startup.loop_entered"]
    assert loop_entered["start"] <= entered[0]
    # the first step's row is its forced span: the same clock reading
    (first,) = [s for s in fit_result.timeline["spans"]
                if s["name"] == "train.step"]
    assert first["start"] == entered[0]
    assert rows[0][2] <= first["end"] - first["start"] + CLOCK_STEP
    # the loop read each loss before it stepped again: from the second
    # step on the device was dry at entry; no profile ran, and the dense
    # model has no metrics of its own to wait for
    from ray_tpu.train import session

    assert [r[4] for r in rows] == [0] + [session.STEP_DEVICE_DRY] * 6
    totals = led["totals"]
    assert totals["dispatch_s"] == pytest.approx(sum(r[2] for r in rows))
    assert totals["report_s"] == pytest.approx(sum(r[3] for r in rows))
    assert totals["wall_s"] == pytest.approx(entered[-1] - entered[0])
    walls = [b - a for a, b in zip(entered, entered[1:])]
    assert totals["longest_wall_s"] == pytest.approx(max(walls))
    assert totals["longest_wall_step"] == 1 + walls.index(max(walls))


def test_a_sleep_in_the_loop_is_the_loops_own_time(fit_result):
    rows = _ledger(fit_result)["rows"]
    step, t, dispatch, report, _ = rows[SLEEPS_AFTER_STEP - 1]
    assert step == SLEEPS_AFTER_STEP
    wall = rows[SLEEPS_AFTER_STEP][1] - t
    # the sleep began after the report's block had ended, and ended before
    # the next step entered: it is in neither other column
    assert wall - dispatch - report >= LOOP_SLEEP_S


def test_a_driver_slow_to_take_a_result_shows_in_report_s(fit_result):
    """The queue holds ONE result: while the driver is held with step 4's,
    the loop puts step 5's and waits with step 6's inside `train.report`."""
    rows = _ledger(fit_result)["rows"]
    held = max(rows[HELD_AT_STEP:], key=lambda r: r[3])
    assert held[0] in (HELD_AT_STEP + 1, HELD_AT_STEP + 2)
    assert held[3] >= DRIVER_HOLD_S / 2
    nxt = rows[held[0]][1] if held[0] < len(rows) else None
    if nxt is not None:     # the wait is the row's report, not its own time
        assert held[3] <= nxt - held[1]
    # .. and no row before the hold waited so long
    assert all(r[3] < held[3] for r in rows[:HELD_AT_STEP - 1])


def test_opsdump_prints_the_ledger_of_a_run(fit_result, capsys):
    import opsdump

    path = os.path.join(fit_result.path, "timeline.json")
    assert opsdump.main(["--timeline", path, "--steps"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("rank0: 7 steps (7 rows kept, 0 dropped)")
    assert "wall of a step, ms: median" in out
    # the first step (its compile), the sleep and the held report are over
    # 1.25 x the median, each with its time in its own column
    table = {int(l.split()[0]): l.split() for l in out.splitlines()
             if l.split() and l.split()[0].isdigit()}
    assert {1, SLEEPS_AFTER_STEP} <= set(table)
    wall, dispatch, report, compiled, own = map(float, table[1][1:6])
    assert compiled > 0 and wall == pytest.approx(
        dispatch + report + compiled + own, abs=0.05)
    wall, dispatch, report, compiled, own = map(
        float, table[SLEEPS_AFTER_STEP][1:6])
    assert own >= LOOP_SLEEP_S * 1e3 - 0.01 and compiled == 0.0
    assert table[SLEEPS_AFTER_STEP][6] == "device_dry"
    held = [row for step, row in table.items() if step > HELD_AT_STEP]
    assert held and max(float(r[3]) for r in held) >= DRIVER_HOLD_S * 500


def test_idle_time_goes_to_the_annotation_over_the_gaps_midpoint():
    """A made profile, its clock 100 s behind the epoch: two steps of one
    second; the device runs [0.1, 0.6] and [0.7, 0.95] of the first and
    [1.3, 2.0] of the second (the profile ends there)."""
    import opsdump

    ns = 1e9
    planes = {
        "/device:TPU:0": {"XLA Modules": [
            ("jit_step", 0.1 * ns, 0.5 * ns, {}),
            ("jit_step", 0.7 * ns, 0.25 * ns, {}),
            ("jit_step", 1.3 * ns, 0.7 * ns, {})]},
        "/host:CPU": {"loop": [
            ("ray_tpu:train.step", 0.0, 0.2 * ns, {"t_epoch": 100.0}),
            ("ray_tpu:train.report", 0.6 * ns, 0.15 * ns, {"t_epoch": 100.6}),
            ("ray_tpu:train.step", 1.0 * ns, 0.2 * ns, {"t_epoch": 101.0}),
            ("bench:sync_loss", 0.2 * ns, 0.4 * ns, {})]},
    }
    offset, spread, n = opsdump.clock_offset(planes)
    assert (offset, n) == (100.0, 3) and spread < 1e-9
    rows = [[1, 100.0, 0.2, 0.15, 1], [2, 101.0, 0.2, 0.0, 1],
            [3, 102.0, 0.2, 0.0, 0]]
    walls = opsdump.step_walls(rows, [])
    (one, two) = opsdump.idle_by_annotation(planes, offset, walls)
    step, report = opsdump.STEP_ANNOTATIONS
    # [0, 0.1] under train.step, [0.6, 0.7] under train.report, [0.95, 1.0]
    # under neither
    assert one["step"] == 1
    assert one["idle"] == pytest.approx(
        {step: 0.1, report: 0.1, "neither": 0.05})
    # [1.0, 1.3]: its midpoint lies under the second step's annotation
    assert two["idle"] == pytest.approx(
        {step: 0.3, report: 0.0, "neither": 0.0})
    # a step the profile does not wholly cover is left out
    assert opsdump.idle_by_annotation(
        planes, offset, opsdump.step_walls(rows + [[4, 103.0, 0, 0, 0]], [])
    ) == [one, two]


@pytest.fixture(scope="module")
def traced_steps():
    """ONE tiny step program in this process, its own ledger: two steps,
    three under a profile, one behind it."""
    import jax

    from ray_tpu.train import session

    tracing.disable_tracing()
    ts, batch = _tiny_train_step()
    state = ts.init(jax.random.key(0))
    kept, session.step_ledger = session.step_ledger, session.StepLedger()
    d = tempfile.mkdtemp(prefix="profile-steps-")
    try:
        for i in range(6):
            if i == 2:
                jax.profiler.start_trace(d)
            state, metrics = ts.step(state, batch)
            float(metrics["loss"])
            if i == 4:
                jax.profiler.stop_trace()
        rows = session.step_ledger.snapshot()["rows"]
    finally:
        session.step_ledger = kept
    (xplane,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    return {"rows": rows, "events": [
        e for e in _host_events(xplane) if e[0] == "ray_tpu:train.step"]}


def test_a_rows_t_enter_is_its_events_t_epoch(traced_steps):
    from ray_tpu.train import session

    rows = traced_steps["rows"]
    assert [r[0] for r in rows] == [1, 2, 3, 4, 5, 6]
    by_step = {e[3]["step"]: e[3]["t_epoch"] for e in traced_steps["events"]}
    # exactly the steps entered under the running profile are in it ..
    assert sorted(by_step) == [3, 4, 5]
    # .. at the very instant their rows hold
    assert by_step == {r[0]: r[1] for r in rows if r[0] in by_step}
    # .. and exactly they are flagged
    assert [r[0] for r in rows if r[4] & session.STEP_PROFILED] == [3, 4, 5]
    for (_, _, dur, _), row in zip(
            sorted(traced_steps["events"], key=lambda e: e[1]), rows[2:5]):
        assert row[2] <= dur / 1e9 + CLOCK_STEP     # the block is inside


def test_the_ring_keeps_the_newest_rows_and_the_totals_of_all():
    from ray_tpu.train import session

    led = session.StepLedger()
    assert session.TIMELINE_MAX_ROWS == 4096
    led.reported(1.0)               # no step before it: adds to no row
    for i in range(1, 5001):
        row = led.enter(i, 1000.0 + 0.5 * i + (2.0 if i > 3000 else 0.0), 0)
        led.dispatched(row, 0.25)
        led.reported(0.0625)
        led.reported(0.0625)
    snap = led.snapshot()
    assert len(snap["rows"]) == 4096 and snap["dropped"] == 904
    assert [snap["rows"][0][0], snap["rows"][-1][0]] == [905, 5000]
    assert snap["rows"][0] == [905, 1000.0 + 0.5 * 905, 0.25, 0.125, 0]
    assert snap["totals"] == {
        "steps": 5000, "wall_s": 4999 * 0.5 + 2.0, "dispatch_s": 1250.0,
        "report_s": 625.0, "longest_wall_s": 2.5, "longest_wall_step": 3000}
    snap["rows"][0][2] = 9.0        # a copy: the ledger's own rows stand
    assert led.snapshot()["rows"][0][2] == 0.25


def test_the_ledger_costs_under_five_microseconds_a_step():
    """What `ShardedTrainStep.step` and `train.report` do for the ledger,
    beside the spans they had: the least of many short rounds (a round of
    3 ms is not always pre-empted beside five other workers)."""
    import jax.numpy as jnp

    from ray_tpu.train import session

    led = session.StepLedger()
    loss = jnp.zeros(())
    loss.block_until_ready()
    n, best = 1_000, float("inf")
    for _ in range(30):
        start = time.perf_counter()
        for i in range(n):
            flags = session.STEP_SYNCED if i & (i - 1) == 0 else 0
            if loss is not None and loss.is_ready():
                flags |= session.STEP_DEVICE_DRY
            entered = [time.time(), False]      # `trace_span` fills it
            t = time.perf_counter()
            row = led.enter(i, entered[0], flags | (
                session.STEP_PROFILED if entered[1] else 0))
            led.dispatched(row, time.perf_counter() - t)
            t = time.perf_counter()
            led.reported(time.perf_counter() - t)
        best = min(best, (time.perf_counter() - start) / n)
    assert led.snapshot()["totals"]["steps"] == 30 * n
    assert best < 5e-6, f"{best * 1e6:.2f} us a step"


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
