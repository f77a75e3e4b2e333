"""DataParallelTrainer / JaxTrainer: the Train-library driver.

Counterpart of the reference's train/data_parallel_trainer.py (:25,
training_loop :428) + train/_internal/backend_executor.py (:67; start :129
creates PG + WorkerGroup, start_training :441 wires sessions,
get_with_failure_handling :675 and _restart :736 for fault tolerance) +
train/trainer.py TrainingIterator (:31).  Collapsed into one driver class:
our worker group already runs sessions worker-side.

JaxTrainer is to this what the reference's TorchTrainer is to
DataParallelTrainer — the JAX backend is the default.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.backend import BackendConfig, JaxBackendConfig
from ray_tpu.train.checkpoint import Checkpoint, StorageContext
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.util import device_stats, tracing

logger = logging.getLogger(__name__)

TIMELINE_FILE = "timeline.json"


class TrainingFailedError(RuntimeError):
    """Training did not complete (worker failures exceeded max_failures, or
    the training loop raised)."""


@dataclasses.dataclass
class Result:
    """Counterpart of python/ray/air/result.py Result."""

    metrics: Optional[Dict[str, Any]]
    checkpoint: Optional[Checkpoint]
    path: str
    error: Optional[BaseException] = None
    metrics_history: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)
    # Where the run's start-up and each of its steps went, as
    # <path>/timeline.json holds it (`_write_timeline`); None when no
    # attempt got as far as its loop.
    timeline: Optional[Dict[str, Any]] = None

    @property
    def best_checkpoints(self):
        return [self.checkpoint] if self.checkpoint else []


def _shard_dataset(ds: Any, num_shards: int) -> List[Any]:
    """Split one dataset into per-worker shards.

    ray_tpu.data Datasets use streaming_split (locality-aware iterators,
    reference dataset.py:1236); plain sequences/arrays are sliced; anything
    else is replicated.
    """
    if hasattr(ds, "streaming_split"):
        return ds.streaming_split(num_shards)
    try:
        n = len(ds)
    except TypeError:
        return [ds] * num_shards
    per = (n + num_shards - 1) // num_shards
    return [ds[i * per:(i + 1) * per] for i in range(num_shards)]


class DataParallelTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        backend_config: Optional[BackendConfig] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.backend_config = backend_config or BackendConfig()
        self.resume_from_checkpoint = resume_from_checkpoint

    # ------------------------------------------------------------------
    def fit(self) -> Result:
        t_entered = time.time()
        if not ray_tpu.is_initialized():
            ray_tpu.init()
        cfg = self.run_config
        storage = StorageContext(
            cfg.storage_path, cfg.name,
            num_to_keep=cfg.checkpoint_config.num_to_keep)
        max_failures = cfg.failure_config.max_failures
        failures = 0
        latest_ckpt = self.resume_from_checkpoint
        history: List[Dict[str, Any]] = []

        # RunConfig.callbacks reach standalone fits too (reference:
        # Train dispatches the same tune Callback surface; SURVEY L6
        # AIR-shared config).  The run is exposed to callbacks as one
        # trial-shaped handle.
        from ray_tpu.tune.callbacks import default_callbacks

        callbacks = default_callbacks(getattr(cfg, "callbacks", None))
        handle = _RunHandle(
            trial_id=storage.name or "train_run",
            trial_dir=storage.run_dir,
            config=dict(self.train_loop_config),
            metrics_history=history)
        callbacks.setup(run_dir=storage.run_dir, trials=[handle])
        callbacks.on_trial_start(trial=handle)
        try:
            while True:
                try:
                    metrics, timeline = self._run_attempt(
                        storage, latest_ckpt, history, t_entered,
                        callbacks=callbacks, handle=handle)
                    callbacks.on_trial_complete(trial=handle)
                    return Result(
                        metrics=metrics,
                        checkpoint=storage.latest_checkpoint(),
                        path=storage.run_dir,
                        metrics_history=history,
                        timeline=timeline)
                except TrainingFailedError:
                    callbacks.on_trial_error(trial=handle)
                    raise
                except Exception as e:
                    failures += 1
                    if max_failures >= 0 and failures > max_failures:
                        callbacks.on_trial_error(trial=handle)
                        if isinstance(e, _UserLoopError):
                            raise TrainingFailedError(str(e)) from e
                        raise TrainingFailedError(
                            f"training failed after {failures} "
                            f"failure(s): {e}") from e
                    # restart from the latest persisted checkpoint
                    latest_ckpt = storage.latest_checkpoint() or latest_ckpt
                    t_entered = time.time()
        finally:
            callbacks.on_experiment_end(trials=[handle])

    # ------------------------------------------------------------------
    def _run_attempt(self, storage: StorageContext,
                     checkpoint: Optional[Checkpoint],
                     history: List[Dict[str, Any]], t_entered: float,
                     callbacks=None, handle=None) -> tuple:
        """One attempt: (rank 0's last metrics, the run's timeline).
        `startup.fit` (from `t_entered`: fit() entered, or the last
        attempt failed) holds the attempt's start-up phases and ends when
        every worker's loop thread has started."""
        from ray_tpu.train.worker_group import WorkerGroup
        from ray_tpu.train.backend import _jax_env

        sc = self.scaling_config
        env = _jax_env(self.backend_config) \
            if isinstance(self.backend_config, JaxBackendConfig) else None
        backend = self.backend_config.backend_cls()
        group = None
        try:
            with tracing.trace_span("startup.fit", force=True,
                                    start=t_entered):
                group = WorkerGroup(
                    sc.num_workers, sc.worker_resources(), storage.run_dir,
                    placement_strategy=sc.placement_strategy, env=env,
                    num_to_keep=self.run_config.checkpoint_config.num_to_keep)
                with tracing.trace_span("startup.backend_on_start",
                                        force=True):
                    backend.on_start(group, self.backend_config)

                shards: Dict[int, Dict[str, Any]] = {
                    i: {} for i in range(sc.num_workers)}
                for name, ds in self.datasets.items():
                    for i, shard in enumerate(
                            _shard_dataset(ds, sc.num_workers)):
                        shards[i][name] = shard

                with tracing.trace_span("startup.start_training",
                                        force=True):
                    backend.on_training_start(group, self.backend_config)
                    ray_tpu.get([
                        w.start_training.remote(
                            self.train_loop_per_worker,
                            self.train_loop_config,
                            checkpoint.as_directory() if checkpoint
                            else None,
                            shards[i], storage.name)
                        for i, w in enumerate(group.workers)
                    ], timeout=120)

            metrics = self._poll_results(group, history,
                                         callbacks=callbacks, handle=handle)
            return metrics, _write_timeline(group, storage.run_dir,
                                            since=t_entered)
        finally:
            if group is not None:
                try:
                    backend.on_shutdown(group, self.backend_config)
                finally:
                    group.shutdown()

    def _poll_results(self, group, history,
                      callbacks=None, handle=None) -> Optional[Dict]:
        finished = set()
        last_rank0: Optional[Dict] = None
        deadline_slack = 600.0  # no single poll may hang longer than this
        while len(finished) < group.num_workers:
            pending = [i for i in range(group.num_workers)
                       if i not in finished]
            refs = {i: group.workers[i].next_result.remote(2.0)
                    for i in pending}
            for i, ref in refs.items():
                item = ray_tpu.get(ref, timeout=deadline_slack)
                if item is None:
                    continue
                if item.get("finished"):
                    finished.add(i)
                    continue
                if "error" in item:
                    raise _UserLoopError(
                        f"rank {i} train loop failed:\n{item['traceback']}")
                if i == 0:
                    last_rank0 = item.get("metrics")
                    entry = dict(item.get("metrics") or {})
                    if item.get("checkpoint_path"):
                        entry["checkpoint_path"] = item["checkpoint_path"]
                        if callbacks is not None:
                            handle.last_checkpoint = \
                                item["checkpoint_path"]
                            callbacks.on_checkpoint(
                                trial=handle,
                                checkpoint_path=item["checkpoint_path"])
                    history.append(entry)
                    if callbacks is not None:
                        callbacks.on_trial_result(trial=handle,
                                                  result=entry)
            time.sleep(0.01)
        return last_rank0


def _write_timeline(group, run_dir: str, since: float
                    ) -> Optional[Dict[str, Any]]:
    """Where the run's start-up went: the driver's forced spans (the
    runtime's, and this attempt's: those begun at or after `since`) merged
    with every worker's (asked once, after the loops have ended), as
    span dicts (`tracing.span_row_to_dict`'s keys) with `worker` and
    `pid`; every `xla.compile` span apart; each process's `compile_totals`;
    each worker's step ledger under `steps` (`session.StepLedger`: one
    row a step of the run, traced or not); after a traced run, rank 0's `programs` (`TrainWorker.timeline`: that
    worker compiles the step program's report then, from the cache, which
    is what the longer wait is for).  Written to
    <run_dir>/timeline.json beside the loggers' result.json and returned
    for `Result.timeline`.  Never fails a finished run."""
    from ray_tpu.train.worker_group import timeline_spans

    try:
        parts = ray_tpu.get([w.timeline.remote() for w in group.workers],
                            timeout=300)
        spans = [s for s in timeline_spans("driver")
                 if s["start"] >= since
                 or s["name"] in tracing.RUNTIME_STARTUP_SPANS]
        totals = {"driver": device_stats.compile_totals()}
        steps = {}
        for part in parts:
            spans.extend(part["spans"])
            totals[f"rank{part['rank']}"] = part["compile_totals"]
            steps[f"rank{part['rank']}"] = part["steps"]
        doc = {"spans": [s for s in spans if s["name"] != "xla.compile"],
               "compiles": [s for s in spans if s["name"] == "xla.compile"],
               "compile_totals": totals, "steps": steps}
        programs = next((p["programs"] for p in parts if "programs" in p),
                        None)
        if programs:
            doc["programs"] = programs
        # Through JSON, so that Result.timeline is what the file holds.
        text = json.dumps(doc, default=str)
        os.makedirs(run_dir, exist_ok=True)
        tmp = os.path.join(run_dir, TIMELINE_FILE + ".tmp")
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, os.path.join(run_dir, TIMELINE_FILE))
        return json.loads(text)
    except Exception:  # noqa: BLE001 — a record of the run, not the run
        logger.warning("could not write %s", TIMELINE_FILE, exc_info=True)
        return None


@dataclasses.dataclass
class _RunHandle:
    """Trial-shaped view of a standalone train run for tune callbacks
    (same attribute surface loggers read: trial_id/trial_dir/config/
    metrics_history)."""

    trial_id: str
    trial_dir: str
    config: Dict[str, Any]
    metrics_history: List[Dict[str, Any]]
    last_checkpoint: Optional[str] = None


class _UserLoopError(RuntimeError):
    """Training-loop exception (as opposed to infrastructure failure)."""


class JaxTrainer(DataParallelTrainer):
    """DataParallelTrainer with the JAX backend by default (reference
    TorchTrainer ↔ DataParallelTrainer relationship, torch_trainer.py)."""

    def __init__(self, train_loop_per_worker, *,
                 backend_config: Optional[JaxBackendConfig] = None, **kw):
        super().__init__(
            train_loop_per_worker,
            backend_config=backend_config or JaxBackendConfig(), **kw)


class TorchTrainer(DataParallelTrainer):
    """DataParallelTrainer with the torch.distributed (gloo) backend
    (reference python/ray/train/torch/torch_trainer.py). Worker loops
    use train.torch_backend.prepare_model / prepare_data_loader."""

    def __init__(self, train_loop_per_worker, *, backend_config=None,
                 **kw):
        from ray_tpu.train.torch_backend import TorchConfig

        super().__init__(
            train_loop_per_worker,
            backend_config=backend_config or TorchConfig(), **kw)
