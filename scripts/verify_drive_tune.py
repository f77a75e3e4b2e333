"""End-to-end drive of the ray_tpu.tune public surface."""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import jax

import ray_tpu
from ray_tpu import tune
from ray_tpu.train import Checkpoint, RunConfig

ray_tpu.init(num_cpus=8)
base = tempfile.mkdtemp()


def objective(config):
    for step in range(3):
        tune.report({"score": -abs(config["x"] - 2.0) - 0.01 * step})


grid = tune.Tuner(
    objective,
    param_space={"x": tune.grid_search([0.0, 2.0, 5.0]),
                 "noise": tune.uniform(0, 1e-6)},
    tune_config=tune.TuneConfig(metric="score", mode="max", num_samples=1),
    run_config=RunConfig(storage_path=base, name="drive"),
).fit()
best = grid.get_best_result()
assert abs(best.metrics["score"] + 0.02) < 1e-3, best.metrics
print("[1] grid search found x=2.0, score:", best.metrics["score"])

state = json.load(open(os.path.join(base, "drive", "experiment_state.json")))
assert all(t["state"] == "TERMINATED" for t in state["trials"])
print("[2] experiment state persisted:", len(state["trials"]), "trials")


def ckpt_fn(config):
    ck = tune.get_checkpoint()
    start = json.load(open(os.path.join(
        ck.as_directory(), "s.json")))["i"] if ck else 0
    for i in range(start, 3):
        d = tempfile.mkdtemp()
        json.dump({"i": i + 1}, open(os.path.join(d, "s.json"), "w"))
        tune.report({"i": i}, checkpoint=Checkpoint.from_directory(d))


grid = tune.Tuner(
    ckpt_fn, param_space={},
    tune_config=tune.TuneConfig(metric="i", mode="max"),
    run_config=RunConfig(storage_path=base, name="ck"),
).fit()
r = grid.get_best_result()
assert r.checkpoint is not None
print("[3] checkpointed trial, final i:", r.metrics["i"])


def asha_fn(config):
    for step in range(1, 16):
        tune.report({"s": config["q"] * step})


grid = tune.Tuner(
    asha_fn,
    param_space={"q": tune.grid_search([0.1, 1.0, 4.0, 16.0])},
    tune_config=tune.TuneConfig(
        metric="s", mode="max",
        scheduler=tune.AsyncHyperBandScheduler(
            grace_period=2, reduction_factor=3, max_t=15)),
    run_config=RunConfig(storage_path=base, name="asha"),
).fit()
iters = sorted(r.metrics.get("training_iteration", 0) for r in grid)
assert iters[0] < 15 and iters[-1] == 15, iters
print("[4] ASHA early-stopped weak trials:", iters)

# [5] TPE adaptive search finds the bowl minimum.
from ray_tpu.tune import TPESearcher


def bowl(config):
    tune.report({"loss": (config["x"] - 0.3) ** 2
                 + (config["y"] + 0.2) ** 2})


tpe_res = tune.Tuner(
    bowl,
    param_space={"x": tune.uniform(-1, 1), "y": tune.uniform(-1, 1)},
    tune_config=tune.TuneConfig(
        metric="loss", mode="min", num_samples=24,
        search_alg=TPESearcher(n_initial=8, seed=0),
        max_concurrent_trials=2),
).fit()
best = tpe_res.get_best_result(metric="loss", mode="min").metrics["loss"]
assert best < 0.1, best
print(f"[5] TPE best loss: {best:.4f}")

ray_tpu.shutdown()
print("TUNE DRIVE OK")
