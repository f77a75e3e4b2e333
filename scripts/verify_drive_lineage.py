"""Drive lineage reconstruction end-to-end through the public API:
lose the only shm copy of task results and watch gets transparently
re-execute the producing chain (reference ObjectRecoveryManager)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # these drives run on the host CPU
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import numpy as np

import ray_tpu
from ray_tpu.core.exceptions import ObjectLostError
from ray_tpu.core.ids import ObjectID

MARK = f"/tmp/verify_lineage_{os.getpid()}"


def lose(rt, ref):
    oid = ObjectID.from_hex(ref.hex())
    rt.core.store.release(oid)
    rt.core.store.delete(oid)


def main():
    open(MARK, "w").close()
    rt = ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def base():
        with open(MARK, "a") as f:
            f.write("b")
        return np.arange(300_000, dtype=np.int64)

    @ray_tpu.remote
    def double(a):
        with open(MARK, "a") as f:
            f.write("d")
        return a * 2

    expected = np.arange(300_000, dtype=np.int64) * 2
    t0 = time.time()
    a = base.remote()
    b = double.remote(a)
    # .copy(): gets are zero-copy views into the arena; the raw view
    # would dangle once we deliberately delete the block below.
    out = ray_tpu.get(b).copy()
    assert (out == expected).all()
    print(f"[1] chain computed in {time.time() - t0:.2f}s, "
          f"runs={open(MARK).read()!r}")

    lose(rt, b)
    out2 = ray_tpu.get(b, timeout=30).copy()
    assert (out2 == expected).all()
    runs = open(MARK).read()
    assert sorted(runs) == ["b", "d", "d"], runs
    print(f"[2] leaf loss -> re-ran only its producer, runs={runs!r}")

    lose(rt, a)
    lose(rt, b)
    out3 = ray_tpu.get(b, timeout=30).copy()
    assert (out3 == expected).all()
    runs = open(MARK).read()
    assert sorted(runs) == ["b", "b", "d", "d", "d"], runs
    print(f"[3] chain loss -> recursive re-run, runs={runs!r}")

    p = ray_tpu.put(np.arange(300_000))
    lose(rt, p)
    try:
        ray_tpu.get(p, timeout=30)
        raise AssertionError("expected ObjectLostError")
    except ObjectLostError as e:
        print(f"[4] put() loss -> ObjectLostError: {str(e)[:60]}...")

    ray_tpu.shutdown()
    os.unlink(MARK)
    print("ALL OK")


if __name__ == "__main__":
    main()
