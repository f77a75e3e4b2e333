"""Device-plane observability (PR 19): util/device_stats.py (backend
probe, compile-event hook, HBM ledger, continuous roofline/MFU), the
gcs._Watchdog device rules, /api/device, the opsdump "device" stream,
the bench trajectory index, and the device-telemetry overhead budget."""

import importlib.util
import json
import os
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import device_stats
from ray_tpu.util import metrics as metrics_mod

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GB = 1024 ** 3


@pytest.fixture(autouse=True)
def _fresh_device_state():
    device_stats.reset()
    device_stats.set_enabled(True)
    yield
    device_stats.reset()
    device_stats.set_enabled(True)


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _load_script(name):
    path = os.path.join(_REPO, "scripts", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Backend probe + CPU fallback (satellite: device: null regression)
# ---------------------------------------------------------------------------

def test_device_sample_null_on_cpu():
    import jax  # noqa: F401  (tier-1 runs under JAX_PLATFORMS=cpu)

    info = device_stats.backend_info()
    if info["backend"] != "cpu":
        pytest.skip(f"accelerator backend {info['backend']!r} present")
    assert not device_stats.has_accelerator()
    # The sampler piggyback NEVER raises on CPU hosts: device is null.
    assert device_stats.device_sample() is None
    fields = device_stats.profile_fields()
    assert "device" in fields and fields["device"] is None
    # The ledger is still a full dict — same shape everywhere.
    led = device_stats.ledger()
    assert led["backend"] == "cpu"
    for key in ("capacity_bytes", "used_bytes", "watermark_fraction",
                "components", "workspace_bytes"):
        assert key in led, led


def test_backend_unloaded_without_jax_import():
    # device_stats must not import jax itself; with jax absent from
    # sys.modules it reports "unloaded" (we can't un-import jax here,
    # so exercise the branch through the module's own probe).
    import sys

    if "jax" in sys.modules:
        saved = sys.modules.pop("jax")
        try:
            assert device_stats.backend_info()["backend"] == "unloaded"
            assert device_stats.device_sample() is None
        finally:
            sys.modules["jax"] = saved
    else:
        assert device_stats.backend_info()["backend"] == "unloaded"


# ---------------------------------------------------------------------------
# Compile-event hook
# ---------------------------------------------------------------------------

def test_compile_hook_counts_shape_churn(monkeypatch):
    import jax

    monkeypatch.setattr(device_stats, "_warmup", 1)
    f = device_stats.count_compiles(jax.jit(lambda x: x * 2),
                                    "churn_local")
    for n in (2, 3, 4, 2):  # three distinct shapes, one cache hit
        f(np.ones(n, dtype=np.float32))
    tbl = device_stats.compile_counts()["churn_local"]
    assert tbl["count"] == 3
    assert tbl["after_warmup"] == 2  # warmup allowance of 1
    assert tbl["last_wall_s"] >= 0.0
    assert tbl["last_shapes"], tbl
    assert device_stats.recompiles_after_warmup() == {"churn_local": 2}
    snap = next(s for s in metrics_mod.local_snapshots()
                if s["name"] == "ray_tpu_recompiles_total")
    assert sum(snap["series"].values()) >= 2.0
    # The wrapper is transparent: jit attributes still reachable.
    assert hasattr(f, "lower")


def test_compile_hook_disabled_is_passthrough():
    import jax

    f = device_stats.count_compiles(jax.jit(lambda x: x + 1),
                                    "disabled_fn")
    device_stats.set_enabled(False)
    f(np.ones(3, dtype=np.float32))
    assert "disabled_fn" not in device_stats.compile_counts()


# ---------------------------------------------------------------------------
# HBM ledger (fake memory_stats) + watermark semantics
# ---------------------------------------------------------------------------

def test_hbm_ledger_with_fake_memory_stats(monkeypatch):
    fake = {"bytes_in_use": 9 * GB, "bytes_limit": 16 * GB,
            "peak_bytes_in_use": 12 * GB}
    monkeypatch.setattr(device_stats, "memory_stats",
                        lambda: dict(fake))
    device_stats.attribute("weights", 6 * GB)
    device_stats.attribute("kv_pages", 2 * GB)
    led = device_stats.ledger()
    assert led["capacity_bytes"] == 16 * GB
    assert led["used_bytes"] == 9 * GB
    assert led["components"] == {"weights": 6 * GB,
                                 "kv_pages": 2 * GB}
    # XLA workspace is the unattributed residual.
    assert led["workspace_bytes"] == 1 * GB
    assert led["watermark_bytes"] == 12 * GB
    assert led["watermark_fraction"] == pytest.approx(0.75)
    # High-watermark: a later, lower peak never lowers it.
    fake["peak_bytes_in_use"] = 8 * GB
    led2 = device_stats.ledger()
    assert led2["watermark_bytes"] == 12 * GB
    assert led2["watermark_fraction"] == pytest.approx(0.75)
    # With a (faked) accelerator the sampler ships the compact view.
    monkeypatch.setattr(device_stats, "has_accelerator", lambda: True)
    samp = device_stats.device_sample()
    assert samp is not None
    assert samp["watermark_fraction"] == pytest.approx(0.75)
    assert samp["components"]["weights"] == 6 * GB


@pytest.mark.parametrize("program, watermark", [
    (0, 9 * GB),                # none said: the allocator's peak
    (15 * GB, 15 * GB),         # a program over the allocator's peak
    (5 * GB, 9 * GB),           # .. and one under it
])
def test_the_watermark_counts_a_running_programs_temporaries(
        monkeypatch, program, watermark):
    """`peak_bytes_in_use` leaves out what a program needs WHILE it runs
    (8.73 GB read after steps of a 16.79 GB program): the watermark is the
    larger of the allocator's peak and what `note_program` was told."""
    device_stats.reset()
    monkeypatch.setattr(device_stats, "memory_stats", lambda: {
        "bytes_in_use": 8 * GB, "bytes_limit": 16 * GB,
        "peak_bytes_in_use": 9 * GB})
    if program:
        device_stats.note_program(program)
    led = device_stats.ledger()
    assert led["watermark_bytes"] == watermark
    assert led["watermark_fraction"] == pytest.approx(watermark / (16 * GB))
    # the most a program ever said stands, and what the allocator reads
    # (`used_bytes`, `workspace_bytes`) is not moved by it
    device_stats.note_program(1 * GB)
    assert device_stats.ledger()["watermark_bytes"] == watermark
    assert led["used_bytes"] == 8 * GB
    device_stats.reset()
    assert device_stats.ledger()["watermark_bytes"] == 9 * GB


def test_a_host_that_reports_no_memory_counts_no_program(monkeypatch):
    """The CPU: capacity is the attribution sum there, and a program's
    bytes against it would be a fraction of nothing."""
    device_stats.reset()
    monkeypatch.setattr(device_stats, "memory_stats", lambda: None)
    device_stats.attribute("weights", 1 * GB)
    device_stats.note_program(4 * GB)
    led = device_stats.ledger()
    assert led["watermark_bytes"] == 1 * GB
    assert led["watermark_fraction"] == pytest.approx(1.0)
    device_stats.reset()


# ---------------------------------------------------------------------------
# Continuous roofline/MFU step hook
# ---------------------------------------------------------------------------

def test_note_step_gauges_and_overrides(monkeypatch):
    monkeypatch.setenv("RAY_TPU_DEVICE_HBM_GBPS", "100")
    monkeypatch.setenv("RAY_TPU_DEVICE_PEAK_TFLOPS", "1")
    frac, mfu = device_stats.note_step(
        tokens_per_s=1000.0, bytes_per_token=1e7,
        flops_per_token=1e8, plane="serve")
    assert frac == pytest.approx(0.1)   # 1e10 B/s over 1e11 B/s
    assert mfu == pytest.approx(0.1)    # 1e11 F/s over 1e12 F/s
    ls = device_stats.last_step()
    assert ls["plane"] == "serve"
    assert ls["roofline_fraction"] == pytest.approx(0.1)
    fields = device_stats.profile_fields()
    assert fields["roofline_fraction"] == pytest.approx(0.1)
    assert fields["mfu"] == pytest.approx(0.1)
    assert fields["tokens_per_s"] == pytest.approx(1000.0)
    for name in ("ray_tpu_device_roofline_fraction",
                 "ray_tpu_device_mfu"):
        snap = next(s for s in metrics_mod.local_snapshots()
                    if s["name"] == name)
        assert snap["series"], name
    # The kill switch short-circuits the whole step path.
    device_stats.set_enabled(False)
    assert device_stats.note_step(
        tokens_per_s=1.0, bytes_per_token=1.0,
        flops_per_token=1.0) == (0.0, 0.0)


def test_unknown_device_kind_emits_no_fraction(monkeypatch):
    """No peak rate is assumed for a device that is not in the table:
    the step is recorded, the fractions are not."""
    monkeypatch.delenv("RAY_TPU_DEVICE_HBM_GBPS", raising=False)
    monkeypatch.delenv("RAY_TPU_DEVICE_PEAK_TFLOPS", raising=False)
    assert device_stats.peak_specs() is None  # device_kind "cpu"
    assert device_stats.note_step(
        tokens_per_s=10.0, bytes_per_token=1e6,
        flops_per_token=1e7) == (None, None)
    ls = device_stats.last_step()
    assert ls["tokens_per_s"] == pytest.approx(10.0)
    assert "roofline_fraction" not in ls and "mfu" not in ls
    assert "mfu" not in device_stats.profile_fields()
    assert device_stats.peak_specs_for("TPU v5 lite") == (819e9, 197e12)
    with pytest.raises(KeyError):
        device_stats.peak_specs_for("TPU v99")


def test_engine_step_sampler_device_fields(monkeypatch):
    monkeypatch.setenv("RAY_TPU_SERVE_STEP_SAMPLE_EVERY", "2")
    # A CPU has no recorded peaks: fractions need them given explicitly.
    monkeypatch.setenv("RAY_TPU_DEVICE_HBM_GBPS", "100")
    monkeypatch.setenv("RAY_TPU_DEVICE_PEAK_TFLOPS", "1")
    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm_engine import LLMEngine

    # Another test file may have compiled these shapes in this process
    # already; the compile counts asserted below need a cold jit cache.
    jax.clear_caches()
    c = tfm.TransformerConfig.tiny()
    eng = LLMEngine(c, page_size=4, num_pages=64, max_batch=4,
                    multi_step=1)
    rng = np.random.default_rng(0)
    for _ in range(4):
        eng.add_request(rng.integers(1, c.vocab_size, 8).tolist(),
                        max_new_tokens=8)
    while eng.has_work():
        eng.step()
    sample = eng.engine_sample
    assert sample is not None
    for key in ("tokens_per_s", "roofline_fraction", "mfu",
                "modeled_bytes_per_token"):
        assert key in sample, sample
    assert sample["tokens_per_s"] > 0
    # The ledger attributes the engine's two resident pools.
    comps = device_stats.ledger()["components"]
    assert comps.get("weights", 0) > 0
    assert comps.get("kv_pages", 0) > 0
    # The wrapped decode entry points counted their warmup compiles.
    counts = device_stats.compile_counts()
    assert any(name.startswith("decoding.") for name in counts), counts
    # The same numbers flow to the continuous gauges.
    ls = device_stats.last_step()
    assert ls is not None and ls["plane"] == "serve"


def test_train_report_step_hook(monkeypatch):
    from ray_tpu.train import session as train_session

    monkeypatch.setenv("RAY_TPU_DEVICE_HBM_GBPS", "100")
    monkeypatch.setenv("RAY_TPU_DEVICE_PEAK_TFLOPS", "1")
    ctx = train_session.TrainContext(
        world_size=1, world_rank=0, local_rank=0, node_rank=0)
    s = train_session._TrainSession(ctx, None)
    drained = []

    def drain():
        drained.append(s.result_queue.get(timeout=5))

    import threading

    for i in range(2):
        t = threading.Thread(target=drain)
        t.start()
        s.report({"loss": 1.0, "tokens_per_sec": 500.0,
                  "bytes_per_token": 2e7, "flops_per_token": 2e8})
        t.join(timeout=5)
    assert len(drained) == 2
    ls = device_stats.last_step()
    assert ls is not None and ls["plane"] == "train"
    assert ls["roofline_fraction"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# Device journal stream -> opsdump lanes
# ---------------------------------------------------------------------------

def test_device_journal_and_opsdump(tmp_path, monkeypatch):
    from ray_tpu.util import journal

    journal.reset()
    monkeypatch.setenv("RAY_TPU_OPS_JOURNAL_DIR", str(tmp_path))
    monkeypatch.setattr(device_stats, "_warmup", 0)
    monkeypatch.setenv("RAY_TPU_DEVICE_HBM_GBPS", "100")
    monkeypatch.setenv("RAY_TPU_DEVICE_PEAK_TFLOPS", "1")
    try:
        device_stats.note_step(tokens_per_s=100.0, bytes_per_token=1e6,
                               flops_per_token=1e7, plane="serve")
        device_stats.note_compile("fn_x", 0.01, [[[4], "float32"]])
        journal.flush_all(timeout=10)
    finally:
        journal.reset()
    envs = journal.replay(str(tmp_path), "device")
    kinds = {e["d"]["kind"] for e in envs}
    assert kinds == {"step", "compile"}

    opsdump = _load_script("opsdump")
    assert "device" in opsdump.STREAMS
    events = opsdump.build_trace(str(tmp_path), streams=("device",))
    counters = [e for e in events if e.get("ph") == "C"]
    instants = [e for e in events if e.get("ph") == "i"]
    assert any(e["name"] == "roofline_fraction[serve]"
               for e in counters), counters
    assert any(e["name"] == "mfu[serve]" for e in counters)
    assert any(e["name"] == "compile fn_x" for e in instants), instants
    # CLI surface: --streams device produces a loadable trace.
    out = tmp_path / "trace.json"
    rc = opsdump.main(["--dir", str(tmp_path), "--streams", "device",
                       "--out", str(out)])
    assert rc == 0
    assert json.loads(out.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# Watchdog device rules + /api/device, end-to-end on a CPU cluster
# ---------------------------------------------------------------------------

def test_device_watchdog_and_api_device(monkeypatch):
    from ray_tpu.util import flight_recorder

    monkeypatch.setenv("RAY_TPU_WATCHDOG_INTERVAL_S", "0.3")
    monkeypatch.setenv("RAY_TPU_DEVICE_RECOMPILE_MAX", "2")
    rt = ray_tpu.init(num_cpus=2)
    try:
        wd = rt.control._watchdog
        assert wd is not None
        assert wd.recompile_max == 2

        @ray_tpu.remote
        def churn():
            import jax
            import numpy as np_
            from ray_tpu.util import device_stats as ds

            f = ds.count_compiles(jax.jit(lambda x: x + 1),
                                  "churn_remote")
            for n in range(1, 9):  # 8 shapes -> 6 past default warmup
                f(np_.ones(n, dtype=np_.float32))
            return ds.recompiles_after_warmup().get("churn_remote", 0)

        after_warmup = ray_tpu.get(churn.remote(), timeout=180)
        assert after_warmup > 2, after_warmup

        # Forced shape churn reaches the head via the profile sampler.
        rt.core.client.call({"op": "set_profile_config",
                             "enabled": True, "interval_s": 0.2})
        deadline = time.time() + 30
        prof = {}
        seen = False
        while time.time() < deadline and not seen:
            prof = rt.core.client.call({"op": "get_profile"})
            seen = any(
                isinstance(s.get("recompiles"), dict)
                and s["recompiles"].get("churn_remote", 0) > 2
                for s in prof.get("workers", {}).values())
            if not seen:
                time.sleep(0.2)
        assert seen, prof

        # Satellite regression: JAX_PLATFORMS=cpu workers emit
        # device: null — present, never raising.
        assert prof["workers"]
        for s in prof["workers"].values():
            assert "device" in s, s
            assert s["device"] is None, s

        deadline = time.time() + 30
        while time.time() < deadline \
                and wd.recompile_storms_flagged == 0:
            time.sleep(0.2)
        assert wd.recompile_storms_flagged >= 1, wd.snapshot()
        storm = [e for e in flight_recorder.dump()
                 if e.get("category") == "health"
                 and e.get("event") == "recompile_storm"]
        assert storm, "no recompile_storm health event"
        assert storm[0]["function"] == "churn_remote"
        assert storm[0]["recompiles_after_warmup"] > 2

        # HBM watermark path with a faked ledger riding an injected
        # profile_report (what a real TPU worker's sampler would ship).
        fake_wh = "f" * 8
        rt.core.client.send({"op": "profile_report", "sample": {
            "ts": time.time(), "pid": 999, "worker": fake_wh,
            "device": {"backend": "tpu",
                       "watermark_fraction": 0.97}}})
        deadline = time.time() + 30
        while time.time() < deadline and wd.hbm_alerts == 0:
            time.sleep(0.2)
        assert wd.hbm_alerts >= 1, wd.snapshot()
        hbm = [e for e in flight_recorder.dump()
               if e.get("event") == "hbm_watermark"]
        assert hbm and hbm[0]["worker"] == fake_wh
        assert hbm[0]["watermark_fraction"] == pytest.approx(0.97)

        # The alert re-arms when occupancy drops back under.
        rt.core.client.send({"op": "profile_report", "sample": {
            "ts": time.time(), "pid": 999, "worker": fake_wh,
            "device": {"backend": "tpu",
                       "watermark_fraction": 0.2}}})
        deadline = time.time() + 30
        while time.time() < deadline and fake_wh in wd._hbm_alerted:
            time.sleep(0.2)
        assert fake_wh not in wd._hbm_alerted

        snap = wd.snapshot()
        assert snap["recompile_storms_flagged"] >= 1
        assert snap["hbm_alerts"] >= 1
        assert snap["recompile_max"] == 2

        # /api/device: live ledger + per-worker device fields +
        # rolling percentiles + device watchdog state, CPU backend OK.
        from ray_tpu.dashboard.http_head import Dashboard

        dash = Dashboard(rt)
        try:
            dev = _get_json(f"{dash.url}/api/device")
            led = dev["local"]["ledger"]
            assert led["backend"] == "cpu"
            for key in ("capacity_bytes", "used_bytes",
                        "watermark_fraction", "components"):
                assert key in led, led
            assert dev["watchdog"]["recompile_storms_flagged"] >= 1
            assert dev["watchdog"]["hbm_alerts"] >= 1
            assert dev["workers"], dev
            assert any(isinstance(w.get("recompiles"), dict)
                       and w["recompiles"].get("churn_remote", 0) > 2
                       for w in dev["workers"].values()), dev["workers"]
            for w in dev["workers"].values():
                assert "device" in w  # null on this CPU cluster
            assert "history" in dev
        finally:
            dash.stop()
    finally:
        ray_tpu.shutdown()
