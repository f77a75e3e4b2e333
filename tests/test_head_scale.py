"""Head scale-out paths: sharded GCS hot paths, the event-driven timer
wheel, O(1)-amortized node selection, and the zero-copy / single-flight
object plane (ISSUE 13).

Covers the shard correctness matrix (N-owner concurrent submit/complete
landing in the right shard), cross-shard PG atomicity, timer-wheel fire
ordering + cancellation, node-manager-level single-flight pull fan-in,
and pickle5 round-trip identity for >= 1 MiB ndarray args.
"""

import os
import threading

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import (
    placement_group,
    remove_placement_group,
)


# ---------------------------------------------------------------------------
# Timer wheel


def test_timer_wheel_fire_ordering():
    from ray_tpu.util.timer_wheel import TimerWheel

    w = TimerWheel(name="test-wheel-order")
    fired = []
    ev = threading.Event()
    # Scheduled out of order; must fire in deadline order.
    w.schedule(0.15, lambda: fired.append("c") or ev.set(), label="c")
    w.schedule(0.05, lambda: fired.append("a"), label="a")
    w.schedule(0.10, lambda: fired.append("b"), label="b")
    assert ev.wait(5.0)
    assert fired == ["a", "b", "c"]
    assert w.fired() == 3
    w.stop()


def test_timer_wheel_cancellation():
    from ray_tpu.util.timer_wheel import TimerWheel

    w = TimerWheel(name="test-wheel-cancel")
    fired = []
    done = threading.Event()
    t1 = w.schedule(0.05, lambda: fired.append("cancelled"))
    t1.cancel()
    assert t1.cancelled
    w.schedule(0.1, lambda: fired.append("kept") or done.set())
    assert done.wait(5.0)
    assert fired == ["kept"]
    # Cancelled timers never count as fired, and drain from pending.
    assert w.fired() == 1
    assert w.pending() == 0
    w.stop()


def test_timer_wheel_exception_isolated():
    """A raising callback must not kill the shared wheel thread."""
    from ray_tpu.util.timer_wheel import TimerWheel

    w = TimerWheel(name="test-wheel-exc")
    done = threading.Event()
    w.schedule(0.01, lambda: 1 / 0)
    w.schedule(0.05, done.set)
    assert done.wait(5.0)
    w.stop()


# ---------------------------------------------------------------------------
# Sharded task table / submit ingress


def test_sharded_task_table_owner_placement():
    """Keys land in the shard their hash names, the dict protocol is
    preserved, and per-shard locks guard distinct shards."""
    from ray_tpu.core.gcs import ShardedTaskTable

    t = ShardedTaskTable(8)
    keys = [f"task-{o}-{i}" for o in range(16) for i in range(32)]
    for k in keys:
        t[k] = k.upper()
    assert len(t) == len(keys)
    for k in keys:
        assert t[k] == k.upper()
        assert k in t
        # lock_for(key) must consistently name one shard per key.
        assert t.lock_for(k) is t.lock_for(k)
    snap = dict(t.items())
    assert len(snap) == len(keys)
    for k in keys[:100]:
        assert t.pop(k) == k.upper()
    assert len(t) == len(keys) - 100


def test_sharded_task_table_concurrent_owners():
    """N owner threads hammering insert/read/pop concurrently: no lost
    updates, no cross-owner interference."""
    from ray_tpu.core.gcs import ShardedTaskTable

    t = ShardedTaskTable(8)
    n_owners, per_owner = 8, 300
    errs = []

    def owner(o):
        try:
            mine = [f"o{o}-t{i}" for i in range(per_owner)]
            for k in mine:
                t[k] = o
            for k in mine:
                assert t[k] == o
            for k in mine[: per_owner // 2]:
                assert t.pop(k) == o
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=owner, args=(o,))
               for o in range(n_owners)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert len(t) == n_owners * (per_owner - per_owner // 2)


def test_concurrent_submit_complete_through_ingress():
    """A multi-threaded submit storm drains through the sharded ingress
    and every task completes with the right result."""
    rt = ray_tpu.init(num_cpus=4)
    try:
        @ray_tpu.remote
        def add(a, b):
            return a + b

        results = {}
        lock = threading.Lock()

        def storm(tid):
            refs = [(i, add.remote(tid, i)) for i in range(25)]
            got = {i: ray_tpu.get(r, timeout=120) for i, r in refs}
            with lock:
                results[tid] = got

        threads = [threading.Thread(target=storm, args=(t,))
                   for t in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(results) == 6
        for tid, got in results.items():
            assert got == {i: tid + i for i in range(25)}
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Cross-shard PG atomicity + node-index placement


def test_pg_strict_spread_atomic_reservation():
    """A STRICT_SPREAD PG reserves all-or-nothing: a second identical PG
    that cannot fully fit must not leak partial reservations, and must
    become ready once the first is removed."""
    c = Cluster(head_node_args={"num_cpus": 1})
    try:
        for i in range(3):
            c.add_node(num_cpus=1, node_id=f"pgnode{i}")
        pg1 = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
        assert pg1.wait(30)
        # All three non-head nodes are fully reserved now.
        pg2 = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
        assert not pg2.wait(2)
        # No partial reservation may have leaked: removing pg1 must free
        # exactly enough for pg2 to become ready.
        remove_placement_group(pg1)
        assert pg2.wait(30)
        remove_placement_group(pg2)
    finally:
        c.shutdown()


def test_pg_spread_lands_on_distinct_nodes():
    """SPREAD via the utilization-bucketed index still spreads bundles
    across distinct nodes when capacity allows."""
    c = Cluster(head_node_args={"num_cpus": 1})
    try:
        for i in range(4):
            c.add_node(num_cpus=2, node_id=f"sp{i}")
        pg = placement_group([{"CPU": 1}] * 4, strategy="SPREAD")
        assert pg.wait(30)
        nodes = {b["node_id"] for b in pg.state()["bundles"]}
        assert len(nodes) == 4, pg.state()
        remove_placement_group(pg)
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# Node-manager-level single-flight pull


def test_nm_pull_object_single_flight():
    """Concurrent pull_object calls for one object fan into ONE wire
    transfer at the node manager; every caller sees the cached replica."""
    from ray_tpu.core import rpc
    from ray_tpu.core.node_manager import NodeManager
    from ray_tpu.core import object_plane

    rt = ray_tpu.init(num_cpus=1)
    nm = None
    try:
        from ray_tpu.core import serialization

        blob = np.arange(400_000, dtype=np.float64)  # ~3.2 MB, not inline
        ref = ray_tpu.put(blob)
        size = serialization.serialize(blob).total_bytes
        # Force the put to land on the head before the NM pulls it.
        assert np.array_equal(np.asarray(ray_tpu.get(ref, timeout=30)),
                              blob)
        nm = NodeManager(rt.address, num_cpus=1, node_id="pullnode")
        cl = rpc.Client(nm.address)
        started_before = object_plane.OBJ.pulls_started
        results = []
        errors = []

        def one_pull():
            try:
                results.append(cl.call(
                    {"op": "pull_object", "obj": ref.hex(),
                     "size": size, "addr": ""}, timeout=60))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=one_pull) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors
        assert len(results) == 4
        assert all(r.get("ok") for r in results)
        assert all(r.get("cached") for r in results)
        started_after = object_plane.OBJ.pulls_started
        # Single flight: the four concurrent calls cost one transfer.
        assert started_after - started_before == 1
        # Repeat pull: already cached, still zero extra transfers.
        r = cl.call({"op": "pull_object", "obj": ref.hex(),
                     "size": size, "addr": ""}, timeout=60)
        assert r.get("cached")
        assert object_plane.OBJ.pulls_started == started_after
        cl.close()
    finally:
        if nm is not None:
            nm.shutdown()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Zero-copy serialization / wire path


def test_pickle5_roundtrip_identity_large_ndarray():
    """>= 1 MiB ndarray args survive the zero-copy path bit-for-bit."""
    rt = ray_tpu.init(num_cpus=2)
    try:
        arr = np.random.default_rng(7).standard_normal(
            200_000).astype(np.float64)  # 1.6 MiB
        assert arr.nbytes >= 1 << 20

        @ray_tpu.remote
        def echo_stats(a):
            return float(a.sum()), a.shape, a.dtype.str, float(a[1234])

        s, shape, dt, probe = ray_tpu.get(echo_stats.remote(arr),
                                          timeout=120)
        assert shape == arr.shape and dt == arr.dtype.str
        assert s == pytest.approx(float(arr.sum()))
        assert probe == float(arr[1234])
        # Round-trip through put/get too (owner-side arena path).
        back = ray_tpu.get(ray_tpu.put(arr), timeout=60)
        assert np.array_equal(np.asarray(back), arr)
    finally:
        ray_tpu.shutdown()


def test_rpc_oob_frames_skip_encoder_copy():
    """Messages with big byte payloads ride KIND_OOB scatter-gather
    frames: the payload round-trips exactly and the zerocopy counter
    advances by at least the payload size."""
    from ray_tpu.core import rpc

    got = {}

    def handler(conn, msg):
        if msg.get("op") == "echo":
            got["n"] = len(msg["data"])
            return {"data": msg["data"]}
        return None

    srv = rpc.Server(host="127.0.0.1", port=0, handler=handler)
    cl = rpc.Client(srv.address)
    try:
        before = rpc.WIRE.zerocopy_bytes
        payload = os.urandom(2 << 20)
        reply = cl.call({"op": "echo", "data": payload}, timeout=30)
        assert reply["data"] == payload
        assert got["n"] == len(payload)
        # Request and response each moved the payload out-of-band.
        assert rpc.WIRE.zerocopy_bytes - before >= 2 * len(payload)
    finally:
        cl.close()
        srv.stop()


def test_put_serialized_skips_reserialize():
    """put_serialized stores the already-encoded bytes (the big-arg
    submit path must not pickle twice)."""
    rt = ray_tpu.init(num_cpus=1)
    try:
        from ray_tpu.core import serialization

        arr = np.arange(150_000, dtype=np.float64)  # 1.2 MiB
        ser = serialization.serialize(arr)
        ref = rt.core.put_serialized(ser)
        back = ray_tpu.get(ref, timeout=60)
        assert np.array_equal(np.asarray(back), arr)
    finally:
        ray_tpu.shutdown()
