"""Training worker group: N actors in a placement group.

Counterpart of the reference's train/_internal/worker_group.py (`WorkerGroup`
:102 — plain Ray actors; execute/execute_async :260/:233) plus the worker-side
half of backend_executor.start_training (:441): each worker hosts a
`_TrainSession` and runs the user loop in a daemon thread, surfacing results
through a polled queue.
"""

from __future__ import annotations

import logging
import os
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.checkpoint import Checkpoint, StorageContext
from ray_tpu.train import session as _session_mod
from ray_tpu.train.session import (
    TIMELINE_MAX_ROWS,
    TrainContext,
    _TrainSession,
)
from ray_tpu.util import device_stats, tracing

# What a worker hands back for the run's timeline: the start-up phases,
# the train programs' spans and every compile event.  Bounded
# (`TIMELINE_MAX_ROWS`): with tracing enabled every step is in the ring.
TIMELINE_PREFIXES = ("startup.", "train.", "xla.compile")
# The program a traced run's timeline reports on: `ShardedTrainStep`'s
# name for its step (`device_stats.count_compiles`).
STEP_PROGRAM = "train.step"

logger = logging.getLogger(__name__)


def timeline_spans(worker: str) -> List[Dict[str, Any]]:
    """This process's timeline spans, oldest first, each with who
    recorded it (`worker`, `pid`)."""
    pid = os.getpid()
    return [{**s, "worker": worker, "pid": pid}
            for s in tracing.get_spans(TIMELINE_PREFIXES)[:TIMELINE_MAX_ROWS]]


class TrainWorker:
    """Actor hosting one training process (rank)."""

    def __init__(self, rank: int, world_size: int, run_dir: str,
                 env: Optional[Dict[str, str]] = None,
                 num_to_keep: Optional[int] = None):
        self.rank = rank
        self.world_size = world_size
        self.run_dir = run_dir
        self.num_to_keep = num_to_keep
        self.session: Optional[_TrainSession] = None
        self.thread: Optional[threading.Thread] = None
        for k, v in (env or {}).items():
            if k == "XLA_FLAGS" and os.environ.get(k):
                # Append, replacing any existing setting of the same flag
                # (a substring test would skip e.g. count=1 when count=12
                # is already present).
                flag_name = v.split("=", 1)[0]
                kept = [f for f in os.environ[k].split()
                        if f.split("=", 1)[0] != flag_name]
                os.environ[k] = " ".join(kept + [v])
            else:
                os.environ[k] = v
        t_start = tracing.process_start_time()
        if t_start is not None:
            tracing.record_span("startup.worker_boot", t_start, time.time(),
                                attributes={"rank": rank}, force=True)

    # -- generic execution (WorkerGroup.execute parity) ---------------------
    def run(self, fn: Callable, *args, **kwargs):
        return fn(*args, **kwargs)

    def node_info(self) -> Dict[str, Any]:
        return {
            "rank": self.rank,
            "pid": os.getpid(),
            "node_id": os.environ.get("RAY_TPU_NODE_ID", ""),
        }

    # -- training lifecycle -------------------------------------------------
    def start_training(self, train_fn: Callable, config: Dict[str, Any],
                       checkpoint_path: Optional[str],
                       dataset_shards: Optional[Dict[str, Any]],
                       experiment_name: str) -> bool:
        ckpt = Checkpoint(checkpoint_path) if checkpoint_path else None
        ctx = TrainContext(
            world_size=self.world_size, world_rank=self.rank,
            local_rank=self.rank, node_rank=self.rank,
            experiment_name=experiment_name)
        self.session = _TrainSession(ctx, ckpt, dataset_shards)
        _session_mod._set_session(self.session)
        storage = StorageContext(
            os.path.dirname(self.run_dir), os.path.basename(self.run_dir),
            num_to_keep=self.num_to_keep)

        def runner():
            s = self.session
            now = time.time()
            tracing.record_span("startup.loop_entered", now, now,
                                attributes={"rank": self.rank}, force=True)
            try:
                if _takes_config(train_fn):
                    train_fn(config)
                else:
                    train_fn()
            except BaseException as e:  # noqa: BLE001 — surfaced to driver
                s.error = e
                s.result_queue.put({
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc(),
                })
            finally:
                s.finished.set()

        # Persist checkpoints worker-side (rank 0), reference
        # storage.py:508 persist_current_checkpoint runs on the worker.
        if self.rank == 0:
            self.session.persist_checkpoint = lambda ckpt, metrics: \
                storage.persist_checkpoint(ckpt.as_directory(), metrics)
        self.thread = threading.Thread(target=runner, daemon=True)
        self.thread.start()
        return True

    def next_result(self, timeout: float = 1.0) -> Optional[Dict[str, Any]]:
        """One queued result, {'finished': True} at end, None if no news."""
        import queue as _q

        s = self.session
        if s is None:
            return None
        try:
            item = s.result_queue.get(timeout=timeout)
        except _q.Empty:
            if s.finished.is_set() and s.result_queue.empty():
                return {"finished": True}
            return None
        if item.get("checkpoint") is not None:
            item["checkpoint_path"] = item.pop("checkpoint").as_directory()
        return item

    def timeline(self) -> Dict[str, Any]:
        """This worker's part of the run's timeline (`JaxTrainer.fit`
        asks once, after the loop has ended): its start-up, train and
        compile spans, `device_stats.compile_totals()` and `steps`, the
        process's step ledger (`session.StepLedger`).  Rank 0 of a
        TRACED run (tracing enabled here, or a profile ran in this
        process) adds `programs`: the step program's report, what a
        device trace's operations are joined to
        (`device_stats.program_report`).  An untraced run lowers
        nothing."""
        part = {"rank": self.rank,
                "spans": timeline_spans(f"rank{self.rank}"),
                "compile_totals": device_stats.compile_totals(),
                "steps": _session_mod.step_ledger.snapshot()}
        if self.rank == 0 and (tracing.is_tracing_enabled()
                               or tracing.profile_seen()):
            try:
                report = device_stats.program_report(STEP_PROGRAM)
            except Exception:  # noqa: BLE001 — a record of the run, not the run
                logger.warning("no report of %s", STEP_PROGRAM, exc_info=True)
                report = None
            if report is not None:
                part["programs"] = {STEP_PROGRAM: report}
        return part

    def shutdown(self) -> bool:
        return True


def _takes_config(fn: Callable) -> bool:
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return True
    positional = [p for p in sig.parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD,
                                p.VAR_POSITIONAL)]
    # Keyword-only / **kwargs-only loops take no config positionally.
    return len(positional) >= 1


class WorkerGroup:
    """N TrainWorker actors, optionally inside a placement group."""

    def __init__(self, num_workers: int, resources_per_worker: Dict[str, float],
                 run_dir: str, placement_strategy: str = "PACK",
                 env: Optional[Dict[str, str]] = None,
                 num_to_keep: Optional[int] = None):
        from ray_tpu.util.placement_group import (
            placement_group,
            remove_placement_group,
        )
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy,
        )

        self.num_workers = num_workers
        self.workers: List = []
        with tracing.trace_span("startup.placement_group", force=True):
            self._pg = placement_group(
                [dict(resources_per_worker) for _ in range(num_workers)],
                strategy=placement_strategy)
            if not self._pg.wait(timeout_seconds=60):
                remove_placement_group(self._pg)
                raise RuntimeError(
                    f"placement group for {num_workers} train workers "
                    f"({resources_per_worker}/worker) not schedulable")
        cls = ray_tpu.remote(TrainWorker)
        # Until every worker process is up and its TrainWorker built: what
        # a spawn costs is then this span's, not the next caller's.
        with tracing.trace_span("startup.worker_spawn", force=True):
            self.workers = [
                cls.options(
                    resources=dict(resources_per_worker),
                    scheduling_strategy=PlacementGroupSchedulingStrategy(
                        placement_group=self._pg,
                        placement_group_bundle_index=i),
                ).remote(i, num_workers, run_dir, env, num_to_keep)
                for i in range(num_workers)
            ]
            try:
                ray_tpu.get([w.node_info.remote() for w in self.workers],
                            timeout=120)
            except BaseException:
                self.shutdown()
                raise

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        return ray_tpu.get(
            [w.run.remote(fn, *args, **kwargs) for w in self.workers],
            timeout=300)

    def execute_single(self, rank: int, fn: Callable, *args, **kwargs):
        return ray_tpu.get(
            self.workers[rank].run.remote(fn, *args, **kwargs), timeout=300)

    def shutdown(self):
        from ray_tpu.util.placement_group import remove_placement_group

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(self._pg)
        except Exception:
            pass
        self.workers = []
