"""End-to-end drive of the ray_tpu.data public surface (library boundary)."""
import os
import sys
import tempfile
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RAY_TPU_CHIPS", "none")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

import ray_tpu
from ray_tpu import data as rd

ray_tpu.init(num_cpus=8)

# read -> fused map chain -> streamed consumption
ds = (rd.range(200, parallelism=8)
      .map_batches(lambda b: {"id": b["id"], "sq": b["id"] ** 2})
      .filter(lambda r: r["id"] % 2 == 0))
total = sum(r["sq"] for r in ds.iter_rows())
assert total == sum(i * i for i in range(0, 200, 2)), total
print("[1] read->map->filter streamed:", total)

# all-to-all: shuffle, sort, groupby
items = rd.from_items([{"k": i % 4, "v": float(i)} for i in range(40)])
srt = [r["v"] for r in items.sort("v", descending=True).take_all()]
assert srt == sorted(srt, reverse=True)
g = {r["k"]: r["sum(v)"] for r in items.groupby("k").sum("v").take_all()}
assert len(g) == 4 and sum(g.values()) == sum(range(40))
print("[2] sort + groupby:", g)

# io roundtrip
d = tempfile.mkdtemp()
items.write_parquet(d)
assert rd.read_parquet(d).count() == 40
print("[3] parquet roundtrip ok")

# streaming_split: two concurrent consumers, equalized
its = rd.range(48, parallelism=6).streaming_split(2, equal=True)
got = [0, 0]


def pull(i):
    got[i] = sum(len(b["id"]) for b in its[i].iter_batches(batch_size=8))


ts = [threading.Thread(target=pull, args=(i,)) for i in range(2)]
[t.start() for t in ts]
[t.join(timeout=120) for t in ts]
assert got == [24, 24], got
print("[4] streaming_split equalized:", got)

# device feed: sharded jax arrays over the virtual mesh
import jax

from ray_tpu.parallel.mesh import build_mesh

mesh = build_mesh(axes={"data": len(jax.devices())})
n = 0
for batch in rd.range(64, parallelism=4).iter_device_batches(
        mesh=mesh, batch_size=16):
    assert not batch["id"].is_fully_replicated
    n += int(batch["id"].shape[0])
assert n == 64
print("[5] iter_device_batches sharded over", len(jax.devices()), "devices")

# [6] preprocessors: fit on a dataset, transform streams through workers,
# transform_batch serves single batches with the same stats.
import numpy as np

from ray_tpu.data.preprocessors import Chain, Concatenator, StandardScaler

ds6 = rd.from_items([{"x": float(i), "y": float(i % 3)} for i in range(20)])
chain = Chain(StandardScaler(columns=["x"]),
              Concatenator(columns=["x", "y"])).fit(ds6)
feats = chain.transform(ds6).take_batch(20)["features"]
assert feats.shape == (20, 2)
assert abs(float(np.asarray(feats)[:, 0].mean())) < 1e-5
one = chain.transform_batch({"x": np.array([9.5]), "y": np.array([1.0])})
assert abs(float(one["features"][0, 0])) < 1e-5  # 9.5 = fitted mean
print("[6] preprocessors fit/transform/transform_batch ok")

ray_tpu.shutdown()
print("DATA DRIVE OK")


def drive_images_and_sql():
    """read_images (fixed + variable shape) and read_sql end to end."""
    import sqlite3
    import tempfile

    import numpy as np
    from PIL import Image

    import ray_tpu
    from ray_tpu import data

    ray_tpu.init(num_cpus=2)  # the main drive shut its runtime down
    with tempfile.TemporaryDirectory() as d:
        for i, hw in enumerate([(8, 6), (10, 12), (6, 6)]):
            Image.new("RGB", (hw[1], hw[0]),
                      color=(i * 20, 0, 0)).save(f"{d}/im{i}.png")
        rows = data.read_images(d, mode="RGB").take_all()
        assert sorted(r["image"].shape for r in rows) == \
            [(6, 6, 3), (8, 6, 3), (10, 12, 3)]
        # Fixed-shape path stacks into dense device-ready batches.
        batches = list(data.read_images(d, size=(4, 5), mode="RGB")
                       .iter_batches(batch_size=3))
        assert batches[0]["image"].shape == (3, 4, 5, 3)
        assert batches[0]["image"].dtype == np.uint8

        db = f"{d}/t.db"
        conn = sqlite3.connect(db)
        conn.execute("CREATE TABLE m (step INTEGER, loss REAL)")
        conn.executemany("INSERT INTO m VALUES (?, ?)",
                         [(i, 5.0 - i) for i in range(4)])
        conn.commit()
        conn.close()
        ds = data.read_sql("SELECT step, loss FROM m ORDER BY step",
                           lambda: sqlite3.connect(db))
        assert ds.count() == 4 and ds.take_all()[-1]["loss"] == 2.0
    print("[images+sql] variable/fixed image reads + SQL rows OK")


def drive_avro_webdataset():
    """Avro OCF + WebDataset tar shards round-trip through the runtime
    (in-tree codecs, no avro/webdataset packages)."""
    import tempfile

    import numpy as np

    from ray_tpu import data

    with tempfile.TemporaryDirectory() as d:
        ds = data.from_items(
            [{"id": i, "name": f"r{i}", "w": 0.5 * i} for i in range(50)])
        files = ds.write_avro(f"{d}/avro")
        back = data.read_avro(files)
        rows = sorted(back.take_all(), key=lambda r: r["id"])
        assert len(rows) == 50 and rows[4]["w"] == 2.0

        wds = data.from_items(
            [{"__key__": f"s{i:03d}", "txt": f"cap {i}", "cls": i,
              "npy": np.arange(3) + i} for i in range(8)])
        shards = wds.write_webdataset(f"{d}/wds")
        out = sorted(data.read_webdataset(shards).take_all(),
                     key=lambda r: r["__key__"])
        assert out[5]["txt"] == "cap 5" and int(out[5]["cls"]) == 5
        np.testing.assert_array_equal(np.asarray(out[5]["npy"]),
                                      np.arange(3) + 5)
    print("[avro+wds] avro OCF + webdataset tar round-trips OK")


drive_images_and_sql()
drive_avro_webdataset()
