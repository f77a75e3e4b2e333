"""End-to-end drive of the ray_tpu public API (library surface)."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # these drives run on the host CPU
os.environ.setdefault("RAY_TPU_CHIPS", "none")
import numpy as np
import ray_tpu

rt = ray_tpu.init(num_cpus=4)
print("[1] init ok, cluster:", ray_tpu.cluster_resources())

@ray_tpu.remote
def add(a, b=0):
    return a + b

print("[2] task:", ray_tpu.get(add.remote(1, b=2)))

# nested + refs in containers
@ray_tpu.remote
def nested(d):
    return ray_tpu.get(d["ref"]) * 10

print("[3] nested w/ container ref:", ray_tpu.get(nested.remote({"ref": ray_tpu.put(7)})))

# large numpy through shm
arr = np.ones((2048, 1024), np.float32)
@ray_tpu.remote
def sum_(x):
    return float(x.sum())
print("[4] 8MB shm arg:", ray_tpu.get(sum_.remote(arr)))

# actors
@ray_tpu.remote(max_concurrency=2)
class Counter:
    def __init__(self, start):
        self.v = start
    def inc(self, n=1):
        self.v += n
        return self.v
    def crash(self):
        raise RuntimeError("actor method boom")

c = Counter.remote(100)
print("[5] actor calls:", ray_tpu.get([c.inc.remote(), c.inc.remote(5)]))
try:
    ray_tpu.get(c.crash.remote())
    print("[6] FAIL - no error raised")
except ray_tpu.TaskError as e:
    print("[6] actor method error propagates:", type(e).__name__)
print("[6b] actor alive after method error:", ray_tpu.get(c.inc.remote()))

# named actor
@ray_tpu.remote(name="registry", max_restarts=0)
class Registry:
    def who(self):
        return "registry-v1"
r = Registry.remote()
ray_tpu.get(r.who.remote())
h = ray_tpu.get_actor("registry")
print("[7] named actor lookup:", ray_tpu.get(h.who.remote()))

# kill
ray_tpu.kill(c)
time.sleep(0.5)
try:
    ray_tpu.get(c.inc.remote(), timeout=5)
    print("[8] FAIL - dead actor call returned")
except Exception as e:
    print("[8] dead actor call raises:", type(e).__name__)

# PROBES
try:
    add(1)  # direct call
except TypeError as e:
    print("[P1] direct call -> TypeError:", str(e)[:50])
try:
    ray_tpu.get("not a ref")
except TypeError as e:
    print("[P2] get(str) -> TypeError")
rt2 = ray_tpu.init(num_cpus=4)
print("[P3] double init returns same runtime:", rt2 is rt)
try:
    ray_tpu.get_actor("ghost")
except ValueError:
    print("[P4] get_actor(missing) -> ValueError")
@ray_tpu.remote(num_returns=2)
def wrong():
    return 1, 2, 3
try:
    ray_tpu.get(wrong.remote())
except ray_tpu.TaskError:
    print("[P5] wrong num_returns -> TaskError")

# async actors: awaits overlap (auto concurrency for coroutine methods).
class AsyncSleeper:
    async def nap(self, t):
        import asyncio
        await asyncio.sleep(t)
        return t

_s = ray_tpu.remote(AsyncSleeper).remote()
ray_tpu.get(_s.nap.remote(0.01))
_t0 = time.time()
assert ray_tpu.get([_s.nap.remote(0.3) for _ in range(8)]) == [0.3] * 8
assert time.time() - _t0 < 1.5, "async awaits did not overlap"
print("[P7] async actor overlapped 8x0.3s naps in %.2fs" % (time.time() - _t0))

# streaming generator tasks: items flow before the task finishes.
@ray_tpu.remote(num_returns="streaming")
def stream(n):
    for i in range(n):
        yield i * 10

got = [ray_tpu.get(r) for r in stream.remote(4)]
assert got == [0, 10, 20, 30], got
print("[P6] streaming generator ->", got)

t0 = time.time()
ray_tpu.shutdown()
print("[9] shutdown in %.2fs" % (time.time() - t0))
print("ALL OK")
