"""Drive the multiprocessing.Pool + joblib backends end-to-end."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # these drives run on the host CPU
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import ray_tpu  # noqa: E402


def main():
    ray_tpu.init(num_cpus=4)
    from ray_tpu.util.multiprocessing import Pool

    def cube(x):
        return x ** 3

    with Pool(processes=3) as p:
        out = p.map(cube, range(50))
        assert out == [i ** 3 for i in range(50)]
        assert p.starmap(pow, [(2, 5), (3, 2)]) == [32, 9]
        assert sorted(p.imap_unordered(cube, range(10))) == \
            sorted(i ** 3 for i in range(10))
    print("[1] Pool map/starmap/imap_unordered over cluster tasks OK")

    from joblib import Parallel, delayed, parallel_backend

    from ray_tpu.util.joblib import register_ray_tpu

    register_ray_tpu()
    t0 = time.time()
    with parallel_backend("ray_tpu", n_jobs=4):
        res = Parallel()(delayed(cube)(i) for i in range(40))
    assert res == [i ** 3 for i in range(40)]
    print(f"[2] joblib backend: 40 delayed calls in {time.time()-t0:.2f}s")
    ray_tpu.shutdown()
    print("ALL OK")


if __name__ == "__main__":
    main()
