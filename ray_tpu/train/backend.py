"""Training backends: per-worker runtime setup before the user loop runs.

Counterpart of the reference's train/backend.py `Backend` ABC (:32,
on_start/on_training_start/on_shutdown) and train/torch/config.py
(`_setup_torch_process_group` :65 — TCP-store rendezvous + NCCL).  The
TPU-native backend swaps the NCCL process group for
`jax.distributed.initialize`: after it, every worker sees the GLOBAL device
set and one jitted program spans the whole mesh — no per-collective process
groups exist to manage (SURVEY.md §3.4 swap point).
"""

from __future__ import annotations

import dataclasses
import socket
from typing import Dict, Optional


@dataclasses.dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    def on_start(self, worker_group, backend_config: BackendConfig):
        pass

    def on_training_start(self, worker_group, backend_config: BackendConfig):
        pass

    def on_shutdown(self, worker_group, backend_config: BackendConfig):
        pass


@dataclasses.dataclass
class JaxBackendConfig(BackendConfig):
    """distributed_init: run jax.distributed.initialize across workers so
    they form one multi-process JAX runtime (None = auto: only when
    num_workers > 1).  host_device_count: force N virtual CPU devices per
    worker (test mode — SURVEY.md §4 blueprint); platform: override
    JAX_PLATFORMS in workers."""

    distributed_init: Optional[bool] = None
    coordinator_port: int = 0
    platform: Optional[str] = None
    host_device_count: Optional[int] = None

    @property
    def backend_cls(self):
        return JaxBackend


def _joins_workers(config: "JaxBackendConfig", num_workers: int) -> bool:
    if config.distributed_init is None:
        return num_workers > 1
    return bool(config.distributed_init)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _jax_env(config: JaxBackendConfig) -> Dict[str, str]:
    env: Dict[str, str] = {}
    if config.platform:
        env["JAX_PLATFORMS"] = config.platform
    if config.host_device_count:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count="
            f"{config.host_device_count}")
    return env


def _start_jax(coordinator: Optional[str], num_processes: int,
               process_id: int, platform: Optional[str]):
    """Runs ON the train worker, before the user's loop: imports JAX,
    joins the workers into one runtime when there is a `coordinator`, and
    makes the process's first device query, which creates the backend
    (on a TPU worker, the TPU client).  The import and the query are the
    worker's `startup.import_jax` and `startup.device_client` spans.

    Env vars (JAX_PLATFORMS / XLA_FLAGS) were already applied by
    TrainWorker.__init__ from _jax_env — the single authoritative path.
    The jax.config override is still needed where a worker forked from a
    warm template had JAX imported, and JAX_PLATFORMS read, before that."""
    from ray_tpu.util import tracing

    with tracing.trace_span("startup.import_jax", force=True):
        import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if coordinator:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id)
    with tracing.trace_span("startup.device_client", force=True):
        devices = jax.devices()
    return {"process_id": jax.process_index(),
            "global_devices": len(devices),
            "local_devices": len(jax.local_devices())}


def _shutdown_jax_distributed():
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    return True


class JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxBackendConfig):
        import ray_tpu

        n = worker_group.num_workers
        do_dist = _joins_workers(backend_config, n)
        if not do_dist and n > 1:
            # distributed_init=False over several workers: the loop joins
            # them itself, and must find no backend made before it does.
            return
        coordinator = None
        if do_dist:
            port = backend_config.coordinator_port or _free_port()
            coordinator = f"127.0.0.1:{port}"
            # TODO multi-node: use rank-0 worker's node IP from node_info().
        refs = [
            w.run.remote(_start_jax, coordinator, n, i,
                         backend_config.platform)
            for i, w in enumerate(worker_group.workers)
        ]
        infos = ray_tpu.get(refs, timeout=120)
        total = infos[0]["global_devices"]
        for info in infos:
            if info["global_devices"] != total:
                raise RuntimeError(
                    "workers disagree on the global device count after "
                    f"jax.distributed init: {infos}")

    def on_shutdown(self, worker_group, backend_config: JaxBackendConfig):
        import ray_tpu

        if not _joins_workers(backend_config, worker_group.num_workers):
            return
        try:
            ray_tpu.get(
                [w.run.remote(_shutdown_jax_distributed)
                 for w in worker_group.workers], timeout=30)
        except Exception:
            pass
