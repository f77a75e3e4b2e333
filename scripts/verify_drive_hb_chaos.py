"""Drive synchronous HyperBand, chaos killers, and non-blocking
profiling through the public API."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # these drives run on the host CPU
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import jax  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import tune  # noqa: E402
from ray_tpu.train import RunConfig  # noqa: E402


def drive_hyperband(run_dir):
    def objective(config):
        for step in range(1, 10):
            tune.report({"score": config["q"] * step})

    grid = tune.Tuner(
        objective,
        param_space={"q": tune.grid_search([0.2, 1.0, 3.0, 9.0])},
        tune_config=tune.TuneConfig(
            metric="score", mode="max",
            scheduler=tune.HyperBandScheduler(max_t=9,
                                              reduction_factor=3),
            max_concurrent_trials=4),
        run_config=RunConfig(storage_path=run_dir, name="hb"),
    ).fit()
    iters = sorted(r.metrics.get("training_iteration", 0) for r in grid)
    assert iters[0] < 9 and iters[-1] == 9, iters
    best = max(r.metrics.get("score", -1) for r in grid)
    assert best == 81.0, best
    print(f"[1] HyperBand: iters={iters} best={best} (culled + survivor)")


def drive_chaos():
    from ray_tpu.util.chaos import WorkerKiller

    @ray_tpu.remote(max_retries=5)
    def square(i):
        time.sleep(0.1)
        return i * i

    killer = WorkerKiller(interval_s=0.4, max_kills=2).start()
    try:
        out = ray_tpu.get([square.remote(i) for i in range(30)],
                          timeout=120)
    finally:
        killer.stop()
    assert out == [i * i for i in range(30)]
    print(f"[2] chaos: 30 tasks survived {len(killer.killed)} worker kill(s)")


def drive_nonblocking_profile():
    """A long trace of one worker must not stall the driver's other
    control-plane calls (Deferred responses on the server)."""
    from ray_tpu.state.api import list_workers, profile_worker

    @ray_tpu.remote
    def nap(s):
        time.sleep(s)
        return s

    ray_tpu.get(nap.remote(0.01))  # warm a pool worker
    target = next(w for w in list_workers()
                  if w["kind"] == "pool" and w["state"] != "dead")
    import threading
    result = {}

    def long_profile():
        result["trace"] = profile_worker(target["worker_id"],
                                         kind="stack", duration_s=0.0)

    t = threading.Thread(target=long_profile)
    t.start()
    # Concurrent control-plane traffic during the profile round-trip.
    t0 = time.time()
    vals = ray_tpu.get([nap.remote(0.05) for _ in range(8)], timeout=60)
    dt = time.time() - t0
    t.join(timeout=60)
    assert vals == [0.05] * 8
    assert "Thread" in result.get("trace", ""), result
    print(f"[3] profile + concurrent tasks ok ({dt:.2f}s for 8 naps)")


def main():
    import tempfile

    rt = ray_tpu.init(num_cpus=4)
    with tempfile.TemporaryDirectory() as d:
        drive_hyperband(d)
    drive_chaos()
    drive_nonblocking_profile()
    ray_tpu.shutdown()
    print("ALL OK")


if __name__ == "__main__":
    main()
