"""Per-worker train session: report / get_checkpoint / get_dataset_shard.

Counterpart of the reference's train/_internal/session.py `_TrainSession`
(:110 — report() :402 queues results to the driver, get_dataset_shard :477)
and the module-level `ray.train.report/get_context` API.  The user training
loop runs in a daemon thread inside the train-worker actor; `report()` hands
(metrics, checkpoint) to the actor's result queue with maxsize-1
backpressure, exactly the reference's result-queue flow (trainer.py:31
TrainingIterator pulls).

The process's step ledger lives here too (`step_ledger`): one row a call
of `ShardedTrainStep.step`, on the program's own clock, which `report()`
adds its time to and `TrainWorker.timeline()` hands to the run's
timeline.json.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional

from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.util import tracing

_session_lock = threading.Lock()
_session: Optional["_TrainSession"] = None

# The most rows of one kind a worker hands back for the run's timeline.
TIMELINE_MAX_ROWS = 4096
# A ledger row's flags: a profile was running when the step entered; a
# counted step, whose span waits for the model's metrics; the previous
# step's loss was ready when this one entered (the host, not the device,
# set this step's start).
STEP_PROFILED, STEP_SYNCED, STEP_DEVICE_DRY = 1, 2, 4


class StepLedger:
    """Every step of the process on its own clock, always on.  A row is
    `[step, t_enter, dispatch_s, report_s, flags]`: the step's number, the
    epoch time its `train.step` span stamps on its annotation as
    `t_epoch`, the seconds inside that span's block (placing the inputs
    and enqueueing the program), the seconds inside the `train.report`
    blocks entered before the next step, and the flags above.  A step's
    wall is the next row's `t_enter` less its own; what of it is neither
    dispatch nor report is the loop's own time.  The ring keeps the newest
    `TIMELINE_MAX_ROWS` rows and counts the rest as `dropped`; the totals
    run over every step, so a long run costs what a short one does."""

    def __init__(self, max_rows: int = TIMELINE_MAX_ROWS):
        self._lock = threading.Lock()   # the loop's thread against a reader
        self._rows: "deque[list]" = deque(maxlen=max_rows)
        self._open: Optional[list] = None
        self._dropped = 0
        self._totals = {"steps": 0, "wall_s": 0.0, "dispatch_s": 0.0,
                        "report_s": 0.0, "longest_wall_s": 0.0,
                        "longest_wall_step": None}

    def enter(self, step: int, t_enter: float, flags: int) -> list:
        """A step has entered: its row, which closes the one before."""
        row = [step, t_enter, 0.0, 0.0, flags]
        totals = self._totals
        with self._lock:
            last = self._open
            if last is not None:
                wall = t_enter - last[1]
                totals["wall_s"] += wall
                if wall > totals["longest_wall_s"]:
                    totals["longest_wall_s"] = wall
                    totals["longest_wall_step"] = last[0]
            if len(self._rows) == self._rows.maxlen:
                self._dropped += 1
            self._rows.append(row)
            self._open = row
            totals["steps"] += 1
        return row

    def dispatched(self, row: list, seconds: float) -> None:
        row[2] = seconds
        self._totals["dispatch_s"] += seconds

    def reported(self, seconds: float) -> None:
        """A `train.report` block's seconds go to the step before it; a
        report with no step before it adds to no row."""
        row = self._open
        if row is not None:
            row[3] += seconds
            self._totals["report_s"] += seconds

    def snapshot(self) -> Dict[str, Any]:
        """{"rows", "dropped", "totals"}, as timeline.json holds them."""
        with self._lock:
            return {"rows": [list(r) for r in self._rows],
                    "dropped": self._dropped, "totals": dict(self._totals)}


step_ledger = StepLedger()


@dataclasses.dataclass
class TrainContext:
    world_size: int
    world_rank: int
    local_rank: int
    node_rank: int
    experiment_name: str = ""

    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_node_rank(self) -> int:
        return self.node_rank

    def get_experiment_name(self) -> str:
        return self.experiment_name


class _TrainSession:
    def __init__(self, context: TrainContext,
                 checkpoint: Optional[Checkpoint],
                 dataset_shards: Optional[Dict[str, Any]] = None):
        self.context = context
        self.loaded_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        # maxsize=1: the loop blocks in report() until the driver consumed
        # the previous result — keeps driver and workers in lockstep.
        self.result_queue: "queue.Queue" = queue.Queue(maxsize=1)
        self.finished = threading.Event()
        self.error: Optional[BaseException] = None
        self._last_report_t: Optional[float] = None
        # (checkpoint, metrics) -> the persisted Checkpoint; set by the
        # worker that persists (rank 0).
        self.persist_checkpoint: Optional[Callable] = None

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None) -> None:
        # The queue holds one item: a driver that is slow to poll blocks
        # the loop here, and the span shows it.
        with tracing.trace_span("train.report"):
            t = time.perf_counter()
            self._note_device_step(metrics)
            if checkpoint is not None and self.persist_checkpoint:
                checkpoint = self.persist_checkpoint(checkpoint, metrics)
            self.result_queue.put({"metrics": dict(metrics),
                                   "checkpoint": checkpoint})
            step_ledger.reported(time.perf_counter() - t)

    def _note_device_step(self, metrics: Dict[str, Any]) -> None:
        """Device-plane step hook (same accounting the serve engine's
        step sampler does): when the loop reports modeled per-step
        work — "step_flops" and/or "step_bytes", or a ready-made
        "tokens_per_sec" with "flops_per_token" — fold it into the
        continuous roofline/MFU gauges tagged plane="train".  Loops
        that report neither pay one dict lookup."""
        now = time.time()
        prev, self._last_report_t = self._last_report_t, now
        flops = metrics.get("step_flops")
        nbytes = metrics.get("step_bytes")
        tok_s = metrics.get("tokens_per_sec")
        if flops is None and nbytes is None and tok_s is None:
            return
        try:
            from ray_tpu.util import device_stats

            if tok_s is not None:
                device_stats.note_step(
                    tokens_per_s=float(tok_s),
                    bytes_per_token=float(
                        metrics.get("bytes_per_token", 0.0)),
                    flops_per_token=float(
                        metrics.get("flops_per_token", 0.0)),
                    plane="train")
            elif prev is not None and now > prev:
                # One report == one step: per-"token" terms collapse to
                # per-step terms at 1/dt steps per second.
                device_stats.note_step(
                    tokens_per_s=1.0 / (now - prev),
                    bytes_per_token=float(nbytes or 0.0),
                    flops_per_token=float(flops or 0.0),
                    plane="train")
        except Exception:  # raylint: allow-swallow(telemetry must never fail a train step report)
            pass


def _set_session(s: Optional[_TrainSession]):
    global _session
    with _session_lock:
        _session = s


def _get_session() -> _TrainSession:
    if _session is None:
        raise RuntimeError(
            "No train session active: ray_tpu.train.report()/get_context() "
            "may only be called inside a training loop run by a Trainer.")
    return _session


# -- public module-level API (ray.train.* parity) ---------------------------

def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    _get_session().report(metrics, checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    return _get_session().loaded_checkpoint


def get_context() -> TrainContext:
    return _get_session().context


def get_dataset_shard(name: str = "train"):
    shard = _get_session().dataset_shards.get(name)
    if shard is None:
        raise KeyError(
            f"no dataset shard {name!r}; pass datasets={{{name!r}: ds}} to "
            f"the Trainer")
    return shard
