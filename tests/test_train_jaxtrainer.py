"""JaxTrainer end-to-end tests (reference model: train/tests with
ray_start_4_cpus fixtures + DummyTrainer, SURVEY.md §4.4)."""

import os
import tempfile

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    TrainingFailedError,
)


@pytest.fixture
def ray4():
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def _run_dir():
    return tempfile.mkdtemp(prefix="ray_tpu_train_")


def test_single_worker_report_and_result(ray4):
    def loop(config):
        ctx = train.get_context()
        for i in range(config["steps"]):
            train.report({"step": i, "loss": 1.0 / (i + 1),
                          "rank": ctx.get_world_rank()})

    res = JaxTrainer(
        loop, train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=_run_dir(), name="single"),
    ).fit()
    assert res.metrics["step"] == 2
    assert res.metrics["rank"] == 0
    assert len(res.metrics_history) == 3


def test_two_workers_context_and_data_shards(ray4):
    data = np.arange(8)

    def loop():
        ctx = train.get_context()
        shard = train.get_dataset_shard("train")
        train.report({"rank": ctx.get_world_rank(),
                      "world": ctx.get_world_size(),
                      "shard_sum": float(np.sum(shard))})

    res = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=_run_dir(), name="two"),
        datasets={"train": data},
        backend_config=train.JaxBackendConfig(distributed_init=False),
    ).fit()
    assert res.metrics["world"] == 2
    # rank 0 got the first half of 0..7
    assert res.metrics["shard_sum"] == float(np.sum(np.arange(4)))


def test_checkpoint_persist_and_result(ray4):
    def loop(config):
        import json

        for i in range(2):
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "state.json"), "w") as f:
                json.dump({"step": i}, f)
            train.report({"step": i},
                         checkpoint=Checkpoint.from_directory(d))

    res = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=_run_dir(), name="ckpt"),
    ).fit()
    assert res.checkpoint is not None
    import json

    with open(os.path.join(res.checkpoint.as_directory(),
                           "state.json")) as f:
        assert json.load(f)["step"] == 1
    assert res.checkpoint.get_metadata()["metrics"]["step"] == 1


def test_failure_recovery_resumes_from_checkpoint(ray4):
    marker = tempfile.mktemp()

    def loop(config):
        import json

        start = 0
        ck = train.get_checkpoint()
        if ck is not None:
            with open(os.path.join(ck.as_directory(), "s.json")) as f:
                start = json.load(f)["step"] + 1
        for i in range(start, 4):
            if i == 2 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                os._exit(1)  # hard-kill the worker process
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "s.json"), "w") as f:
                json.dump({"step": i}, f)
            train.report({"step": i, "resumed_from": start},
                         checkpoint=Checkpoint.from_directory(d))

    res = JaxTrainer(
        loop, train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=_run_dir(), name="recover",
                             failure_config=FailureConfig(max_failures=1)),
    ).fit()
    assert res.metrics["step"] == 3
    assert res.metrics["resumed_from"] == 2  # resumed, not restarted


def test_user_error_raises_training_failed(ray4):
    def loop():
        raise ValueError("boom in user loop")

    with pytest.raises(TrainingFailedError, match="boom"):
        JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(storage_path=_run_dir(), name="err"),
        ).fit()


def test_jax_loop_trains_mlp(ray4):
    """Real jitted training inside the worker (single worker, CPU)."""

    def loop(config):
        import jax
        import jax.numpy as jnp
        import optax

        key = jax.random.key(0)
        w = jnp.zeros((4,))
        xs = jax.random.normal(key, (64, 4))
        ys = xs @ jnp.array([1.0, -2.0, 3.0, 0.5])
        opt = optax.sgd(0.1)
        opt_state = opt.init(w)

        @jax.jit
        def step(w, opt_state):
            def loss(w):
                return jnp.mean((xs @ w - ys) ** 2)

            l, g = jax.value_and_grad(loss)(w)
            up, opt_state = opt.update(g, opt_state)
            return optax.apply_updates(w, up), opt_state, l

        for i in range(50):
            w, opt_state, l = step(w, opt_state)
        train.report({"loss": float(l)})

    res = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=_run_dir(), name="mlp"),
    ).fit()
    assert res.metrics["loss"] < 0.05


def test_multiprocess_jax_distributed_collective(ray4):
    """Two worker processes form ONE jax runtime (4 virtual CPU devices
    each -> 8 global); a jitted sum over a data-sharded global array runs a
    real cross-process collective — the TPU multi-host path (SURVEY.md §3.4
    swap point) exercised on CPU."""

    def loop():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec

        from ray_tpu.train import get_mesh

        mesh = get_mesh({"data": -1})
        sharding = NamedSharding(mesh, PartitionSpec("data"))
        local = np.full((4,), float(jax.process_index() + 1))
        arr = jax.make_array_from_process_local_data(
            sharding, local, global_shape=(8,))
        total = jax.jit(jnp.sum, out_shardings=NamedSharding(
            mesh, PartitionSpec()))(arr)
        train.report({"total": float(total),
                      "ndev": len(jax.devices()),
                      "nlocal": len(jax.local_devices())})

    res = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=_run_dir(), name="mp"),
        backend_config=train.JaxBackendConfig(
            distributed_init=True, platform="cpu", host_device_count=4),
    ).fit()
    assert res.metrics["ndev"] == 8
    assert res.metrics["nlocal"] == 4
    assert res.metrics["total"] == 4 * 1.0 + 4 * 2.0


@pytest.mark.parametrize("short_rank", [0, 1])
def test_the_processes_of_one_mesh_take_one_remat_rung(ray4, time_limit,
                                                       short_rank):
    """PR 40: what `remat_policy="full"` keeps is ONE decision for the
    processes of a mesh.  Two processes, one mesh over both; one of them
    reads a limit its program does not fit (chips differ), the other a
    limit it fits: alone they would build two different programs and the
    job would hang.  Both take the second rung, whichever rank is short."""
    time_limit(240)
    out = tempfile.mkdtemp(prefix="remat_ranks_")

    def loop(config):
        import jax

        from ray_tpu.models import transformer as tfm
        from ray_tpu.ops import dispatch
        from ray_tpu.train import get_mesh
        from ray_tpu.train.train_state import ShardedTrainStep
        from ray_tpu.util import device_stats

        rank = jax.process_index()
        limit = 1000 if rank == config["short_rank"] else 1 << 40
        device_stats.memory_stats = lambda device=None: {
            "bytes_limit": limit, "bytes_in_use": 0}
        cfg = tfm.TransformerConfig.tiny(num_layers=2, max_seq_len=64)
        ts = ShardedTrainStep(cfg, get_mesh({"fsdp": -1}))
        state = ts.init(jax.random.key(0))
        tokens = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 65), dtype=np.int32)
        for _ in range(2):
            state, metrics = ts.step(state, {"tokens": tokens})
        record, = dispatch.taken()["train.remat"]
        with open(os.path.join(config["out"], f"rank{rank}"), "w") as f:
            f.write(f"{ts._keep} {record.split(',program')[0]} "
                    f"{record.rsplit('of', 1)[1]}")
        train.report({"loss": float(metrics["loss"])})

    res = JaxTrainer(
        loop, train_loop_config={"out": out, "short_rank": short_rank},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=_run_dir(), name="remat_ranks"),
        backend_config=train.JaxBackendConfig(
            distributed_init=True, platform="cpu", host_device_count=2),
    ).fit()
    assert np.isfinite(res.metrics["loss"])
    said = {f: open(os.path.join(out, f)).read() for f in os.listdir(out)}
    # each rank read its own limit, and both run the bare program
    assert said == {
        f"rank{short_rank}": "False kept:none 1000,beside0",
        f"rank{1 - short_rank}": f"False kept:none {1 << 40},beside0"}


def test_checkpoint_numbering_survives_restart_and_num_to_keep(ray4):
    """Restarted attempts continue checkpoint numbering (no overwrite) and
    num_to_keep GC runs on the persisting worker."""
    from ray_tpu.train import CheckpointConfig

    marker = tempfile.mktemp()

    def loop(config):
        start = 0
        ck = train.get_checkpoint()
        if ck is not None:
            start = int(open(os.path.join(
                ck.as_directory(), "s.txt")).read()) + 1
        for i in range(start, 4):
            if i == 2 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                os._exit(1)
            d = tempfile.mkdtemp()
            open(os.path.join(d, "s.txt"), "w").write(str(i))
            train.report({"step": i},
                         checkpoint=Checkpoint.from_directory(d))

    run_dir = _run_dir()
    res = JaxTrainer(
        loop, train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            storage_path=run_dir, name="seq",
            failure_config=FailureConfig(max_failures=1),
            checkpoint_config=CheckpointConfig(num_to_keep=2)),
    ).fit()
    # final checkpoint holds step 3 (post-crash work), not stale state
    assert open(os.path.join(
        res.checkpoint.as_directory(), "s.txt")).read() == "3"
    # only num_to_keep checkpoints remain
    kept = [d for d in os.listdir(os.path.join(run_dir, "seq"))
            if d.startswith("checkpoint_")]
    assert len(kept) == 2, kept


def test_async_checkpoint_overlaps_and_roundtrips(tmp_path):
    """save_pytree_async returns before the write completes (after
    warmup), wait() makes it durable, and the restore matches."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.train.checkpoint import load_pytree, save_pytree_async

    tree = {"w": jnp.arange(1_000_000, dtype=jnp.float32).reshape(
        1000, 1000), "step": jnp.asarray(3)}
    # Warmup save (first call pays orbax initialization).
    save_pytree_async(tree, str(tmp_path / "warm")).wait()

    t0 = time.perf_counter()
    h = save_pytree_async(tree, str(tmp_path / "ck"), step=3)
    submit_s = time.perf_counter() - t0
    path = h.wait()
    total_s = time.perf_counter() - t0
    # Real asynchrony: submission must be a small fraction of the full
    # durable write (measured ~50ms vs ~2s). Skip the ratio when the
    # whole write finished too fast to measure overlap meaningfully
    # (tmpfs-fast storage would make any ratio assertion a coin flip).
    if total_s > 0.25:
        assert submit_s < total_s / 2, (submit_s, total_s)
    back = load_pytree(path)
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))
    assert int(back["step"]) == 3
