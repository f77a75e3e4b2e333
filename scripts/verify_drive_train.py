"""End-to-end drive of ray_tpu.train public entry points (verify skill)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import tempfile

import numpy as np

import ray_tpu
from ray_tpu import train
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

ray_tpu.init(num_cpus=4)
run_dir = tempfile.mkdtemp(prefix="vdt_")


def loop(config):
    import jax
    import jax.numpy as jnp

    ctx = train.get_context()
    shard = train.get_dataset_shard("train")
    w = jnp.zeros(())
    for i in range(3):
        w = jax.jit(lambda w: w + jnp.sum(jnp.asarray(shard)))(w)
        d = tempfile.mkdtemp()
        open(os.path.join(d, "w.txt"), "w").write(str(float(w)))
        train.report({"i": i, "w": float(w), "rank": ctx.get_world_rank()},
                     checkpoint=train.Checkpoint.from_directory(d))


res = JaxTrainer(
    loop,
    scaling_config=ScalingConfig(num_workers=2),
    run_config=RunConfig(storage_path=run_dir, name="drive"),
    datasets={"train": np.arange(8).astype(np.float32)},
    backend_config=train.JaxBackendConfig(
        distributed_init=True, platform="cpu", host_device_count=2),
).fit()
print("[1] fit result:", res.metrics)
assert res.metrics["i"] == 2 and res.metrics["rank"] == 0
assert res.checkpoint is not None
print("[2] checkpoint:", open(os.path.join(
    res.checkpoint.as_directory(), "w.txt")).read())
print("[3] history len:", len(res.metrics_history))

# [4] TorchTrainer: 2-worker gloo DDP with synchronized replicas.
from ray_tpu.train import ScalingConfig, TorchTrainer
from ray_tpu.train import session as train_session


def torch_loop(config):
    import torch
    import torch.distributed as dist
    import torch.nn as nn

    from ray_tpu.train.torch_backend import prepare_model

    torch.manual_seed(0)
    model = prepare_model(nn.Linear(2, 1))
    opt = torch.optim.SGD(model.parameters(), lr=0.05)
    rank = train_session.get_context().get_world_rank()
    g = torch.Generator().manual_seed(rank)
    X = torch.randn(32, 2, generator=g)
    y = X @ torch.tensor([[2.0], [-1.0]])
    for _ in range(40):
        opt.zero_grad()
        ((model(X) - y) ** 2).mean().backward()
        opt.step()
    w = (model.module if hasattr(model, "module") else model).weight
    gathered = [None, None]
    dist.all_gather_object(gathered, w.detach().numpy().tolist())
    train_session.report({"weights": gathered})


tres = TorchTrainer(
    torch_loop,
    scaling_config=ScalingConfig(num_workers=2,
                                 resources_per_worker={"CPU": 1})).fit()
w0, w1 = tres.metrics["weights"]
assert w0 == w1, (w0, w1)
print("[4] TorchTrainer DDP replicas in sync:", w0)

ray_tpu.shutdown()


def drive_async_checkpoint():
    """Async orbax checkpointing overlapping a live train loop."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.train.checkpoint import load_pytree, save_pytree_async

    @jax.jit
    def step(w, x):
        g = jax.grad(lambda w: jnp.mean((x @ w - 1.0) ** 2))(w)
        return w - 0.1 * g

    w = jnp.zeros((256, 256))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 256)),
                    dtype=jnp.float32)
    w = step(w, x)  # compile
    d = tempfile.mkdtemp(prefix="vdt_ck_")
    save_pytree_async({"w": w}, d + "/warm").wait()  # orbax warmup
    t0 = time.perf_counter()
    h = save_pytree_async({"w": w, "meta": jnp.asarray(5)},
                          d + "/ck", step=5)
    submit = time.perf_counter() - t0
    for _ in range(20):  # train while the write flushes
        w = step(w, x)
    float(w[0, 0])
    path = h.wait()
    total = time.perf_counter() - t0
    back = load_pytree(path)
    assert int(back["meta"]) == 5 and back["w"].shape == (256, 256)
    print(f"[5] async ckpt: submit {submit*1e3:.0f}ms, 20 train steps "
          f"overlapped the {total*1e3:.0f}ms durable write; restore OK")


drive_async_checkpoint()
print("ALL OK")
