"""Tests for the host-tier collective API (ray_tpu/util/collective.py).

Mirrors the reference's test surface for ray.util.collective
(python/ray/util/collective/ tests): group init (explicit + declarative),
allreduce/allgather/reducescatter/broadcast, send/recv, barrier.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import collective
from ray_tpu.util.collective import ReduceOp


@ray_tpu.remote(num_cpus=0.1)
class Member:
    def __init__(self, world_size, rank, group="default"):
        collective.init_collective_group(
            world_size, rank, backend="host", group_name=group)
        self.rank = rank
        self.group = group

    def allreduce(self, value, op_name="sum"):
        op = {"sum": ReduceOp.SUM, "product": ReduceOp.PRODUCT,
              "min": ReduceOp.MIN, "max": ReduceOp.MAX}[op_name]
        return collective.allreduce(
            np.asarray(value, dtype=np.float32), group_name=self.group, op=op)

    def allgather(self, value):
        return collective.allgather(
            np.asarray(value, dtype=np.float32), group_name=self.group)

    def reducescatter(self, value):
        return collective.reducescatter(
            np.asarray(value, dtype=np.float32), group_name=self.group)

    def broadcast(self, value, src):
        return collective.broadcast(
            np.asarray(value, dtype=np.float32), src_rank=src,
            group_name=self.group)

    def send(self, value, dst):
        collective.send(np.asarray(value, dtype=np.float32), dst,
                        group_name=self.group)
        return True

    def recv(self, src):
        return collective.recv(src, group_name=self.group)

    def barrier_then_rank(self):
        collective.barrier(group_name=self.group)
        return collective.get_rank(group_name=self.group)


@pytest.fixture
def members(ray_start_regular):
    ms = [Member.remote(3, r, "g3") for r in range(3)]
    yield ms
    for m in ms:
        ray_tpu.kill(m)


def test_allreduce_sum(members):
    outs = ray_tpu.get(
        [m.allreduce.remote([1.0, 2.0]) for m in members])
    for out in outs:
        np.testing.assert_allclose(out, [3.0, 6.0])


def test_allreduce_max(members):
    outs = ray_tpu.get(
        [m.allreduce.remote(float(i + 1), "max")
         for i, m in enumerate(members)])
    for out in outs:
        assert float(out) == 3.0


def test_allgather_orders_by_rank(members):
    outs = ray_tpu.get(
        [m.allgather.remote(float(10 * (i + 1)))
         for i, m in enumerate(members)])
    for out in outs:
        assert [float(x) for x in out] == [10.0, 20.0, 30.0]


def test_reducescatter_shards(members):
    # each rank contributes ones(6); reduced = 3s; rank r gets rows [2r,2r+2)
    outs = ray_tpu.get(
        [m.reducescatter.remote(np.ones(6)) for m in members])
    for out in outs:
        np.testing.assert_allclose(out, [3.0, 3.0])
        assert out.shape == (2,)


def test_broadcast_from_rank1(members):
    outs = ray_tpu.get(
        [m.broadcast.remote(float(i * 100), 1)
         for i, m in enumerate(members)])
    for out in outs:
        assert float(out) == 100.0


def test_send_recv(members):
    r_send = members[0].send.remote([7.0, 8.0], 2)
    r_recv = members[2].recv.remote(0)
    assert ray_tpu.get([r_send])[0] is True
    np.testing.assert_allclose(ray_tpu.get([r_recv])[0], [7.0, 8.0])


def test_barrier_and_rank(members):
    outs = ray_tpu.get([m.barrier_then_rank.remote() for m in members])
    assert sorted(outs) == [0, 1, 2]


def test_multiple_sequential_ops_reuse_group(members):
    for round_ in range(3):
        outs = ray_tpu.get(
            [m.allreduce.remote(float(round_)) for m in members])
        for out in outs:
            assert float(out) == 3.0 * round_


@ray_tpu.remote(num_cpus=0.1)
class DeclMember:
    def use(self, value):
        # No explicit init: the declarative group decl is resolved lazily.
        return collective.allreduce(
            np.asarray(value, dtype=np.float32), group_name="decl-g")


def test_declarative_create_collective_group(ray_start_regular):
    actors = [DeclMember.remote() for _ in range(2)]
    collective.create_collective_group(
        actors, world_size=2, ranks=[0, 1], group_name="decl-g")
    outs = ray_tpu.get([a.use.remote(2.0) for a in actors])
    for out in outs:
        assert float(out) == 4.0
    for a in actors:
        ray_tpu.kill(a)


def test_init_validations(ray_start_regular):
    with pytest.raises(ValueError):
        collective.init_collective_group(2, 5, group_name="bad")
    for unknown in ("mpi", "kv"):
        with pytest.raises(ValueError):
            collective.init_collective_group(2, 0, backend=unknown,
                                             group_name="bad2")
    with pytest.raises(collective.CollectiveGroupError):
        collective.allreduce(np.ones(2), group_name="never-made")


# -- the ring at 8 ranks x 16 MB -------------------------------------------
# The `members` fixture above is 3 ranks and a few floats.  Here every
# rank builds a 16 MB array whose values depend on rank AND position
# (powers of two with exponents 0..3 at a period prime to the chunk
# size), so every reduction is exact in float32 in any order and a chunk
# landing in the wrong place or rank is seen.  Each rank checks its own
# result against the closed form and returns only the mismatch count.

_RING_WORLD = 8


@ray_tpu.remote(num_cpus=0.5)
class RingMember:
    # Self-contained: a worker cannot import this test module, so the
    # class may name nothing of it but its imports.
    WORLD = 8
    ELEMS = (16 << 20) // 4

    def __init__(self, rank):
        collective.init_collective_group(
            self.WORLD, rank, backend="host", group_name="ring8")
        self.rank = rank

    def _input(self, rank):
        e = (np.arange(self.ELEMS) % 251 + 3 * rank) % 4
        return np.exp2(e).astype(np.float32)

    def _expected(self, op_name):
        red = {"sum": np.add, "product": np.multiply,
               "min": np.minimum, "max": np.maximum}[op_name]
        out = self._input(0)
        for r in range(1, self.WORLD):
            out = red(out, self._input(r))
        return out

    def run(self, coll, op_name):
        """Mismatching elements of this rank's result (0 = exact)."""
        mine = self._input(self.rank)
        op = ReduceOp(op_name) if op_name else None
        if coll == "allreduce":
            out = collective.allreduce(mine, group_name="ring8", op=op)
            want = self._expected(op_name)
        elif coll == "reducescatter":
            out = collective.reducescatter(mine, group_name="ring8", op=op)
            want = np.split(self._expected(op_name), self.WORLD)[self.rank]
        elif coll == "allgather":
            parts = collective.allgather(mine, group_name="ring8")
            assert len(parts) == self.WORLD
            return sum(int(np.count_nonzero(p != self._input(r)))
                       for r, p in enumerate(parts))
        else:  # broadcast from rank 5
            out = collective.broadcast(mine, src_rank=5, group_name="ring8")
            want = self._input(5)
        assert out.shape == want.shape and out.dtype == want.dtype
        return int(np.count_nonzero(out != want))


@pytest.fixture(scope="module")
def ring8():
    ray_tpu.init(num_cpus=8)
    try:
        yield [RingMember.remote(r) for r in range(_RING_WORLD)]
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("coll,op_name", [
    ("allreduce", "sum"), ("allreduce", "product"),
    ("allreduce", "min"), ("allreduce", "max"),
    ("reducescatter", "sum"), ("reducescatter", "max"),
    ("allgather", ""), ("broadcast", ""),
])
def test_ring_8_ranks_16mb(ring8, coll, op_name):
    bad = ray_tpu.get([m.run.remote(coll, op_name) for m in ring8],
                      timeout=300)
    assert bad == [0] * _RING_WORLD
