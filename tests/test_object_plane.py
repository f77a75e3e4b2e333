"""Object-plane fast path: windowed chunk pulls (rpc.pull_object_chunked),
single-flight dedup (object_plane.PullManager), direct-into-arena caching
(object_plane.pull_into_store), and locality-aware placement
(gcs.ControlServer._pick_node_indexed's locality consult)."""

import os
import threading
import time

import pytest

import ray_tpu  # noqa: F401 — package import sanity
from ray_tpu.core import gcs, object_plane, rpc
from ray_tpu.core.gcs import READY, NodeState, ObjectEntry
from ray_tpu.core.ids import ObjectID
from ray_tpu.core.object_store import ShmObjectStore
from ray_tpu.core.resources import ResourceSet
from ray_tpu.core.task_spec import TaskArg

CHUNK = 1 << 20  # pull_object_chunked clamps the chunk floor to 1 MiB


def make_payload(size: int) -> bytes:
    # Pattern varies across the whole object, so a chunk landing at the
    # wrong offset cannot produce identical bytes.
    if size == 0:
        return b""
    block = bytes((i * 31 + (i >> 10)) & 0xFF for i in range(min(size, 65536)))
    reps = -(-size // len(block))
    return (block * reps)[:size]


class _ChunkHost:
    """fetch_chunk server over one in-memory payload, with fault hooks."""

    def __init__(self, payload: bytes):
        self.payload = payload
        self.lock = threading.Lock()
        self.requests = []  # (offset, length) in arrival order
        self.served = 0
        self.fail_after = None  # serve N chunks, then raise
        self.die_after = None   # serve N chunks, then kill the connection
        self.short_after = None  # serve N chunks, then a truncated chunk
        self.empty_after = None  # serve N chunks, then b""
        self.delay = 0.0

    def __call__(self, conn, msg):
        if msg.get("op") != "fetch_chunk":
            return None
        with self.lock:
            self.requests.append((msg["offset"], msg["length"]))
            n_served = self.served
        if self.delay:
            time.sleep(self.delay)
        if self.die_after is not None and n_served >= self.die_after:
            conn.sock.close()  # peer death: the serve loop tears down
            raise OSError("connection closed by test")
        if self.fail_after is not None and n_served >= self.fail_after:
            raise ValueError("injected chunk failure")
        part = self.payload[msg["offset"]:msg["offset"] + msg["length"]]
        if self.empty_after is not None and n_served >= self.empty_after:
            part = b""
        elif self.short_after is not None and n_served >= self.short_after:
            part = part[: max(0, len(part) - 1)]
        with self.lock:
            self.served += 1
        return part


def _serve(payload: bytes):
    host = _ChunkHost(payload)
    srv = rpc.Server(host)
    return srv, host


# ---------------------------------------------------------------------------
# Windowed pull correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [0, 1, 1000, CHUNK, CHUNK + 1,
                                  3 * CHUNK - 17, 4 * CHUNK])
@pytest.mark.parametrize("window", [1, 3, 4])
def test_windowed_pull_matches_payload(size, window):
    payload = make_payload(size)
    srv, host = _serve(payload)
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    try:
        got = rpc.pull_object_chunked(client, "ab" * 14, size, CHUNK,
                                      window=window)
        assert got == payload
        # Offsets covered exactly once, in ascending order.
        offs = [o for o, _ in host.requests]
        assert offs == sorted(set(offs))
        assert sum(n for _, n in host.requests) == size
    finally:
        client.close()
        srv.stop()


def test_pull_into_caller_buffer_returns_none():
    size = 2 * CHUNK + 123
    payload = make_payload(size)
    srv, _ = _serve(payload)
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    try:
        dest = bytearray(size + 7)  # larger than needed is fine
        out = rpc.pull_object_chunked(client, "cd" * 14, size, CHUNK,
                                      window=4, into=dest)
        assert out is None
        assert bytes(dest[:size]) == payload
    finally:
        client.close()
        srv.stop()


def test_window_controls_inflight_depth():
    """window=1 keeps exactly one request outstanding (the legacy
    ping-pong wire, byte for byte); window=4 keeps up to 4."""
    size = 6 * CHUNK
    payload = make_payload(size)
    for window, expected_max in ((1, 1), (4, 4)):
        srv, _ = _serve(payload)
        client = rpc.Client(f"127.0.0.1:{srv.port}")
        try:
            orig = client.call_async
            peaks = []

            def spy(msg, _orig=orig, _c=client, _p=peaks):
                pending = _orig(msg)
                _p.append(len(_c._pending))
                return pending

            client.call_async = spy
            got = rpc.pull_object_chunked(client, "ef" * 14, size, CHUNK,
                                          window=window)
            assert got == payload
            assert max(peaks) == expected_max
        finally:
            client.close()
            srv.stop()


def test_pull_window_env_parsing(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PULL_WINDOW", "9")
    assert rpc.pull_window() == 9
    monkeypatch.setenv("RAY_TPU_PULL_WINDOW", "0")
    assert rpc.pull_window() == 1  # floor at the legacy serial wire
    monkeypatch.setenv("RAY_TPU_PULL_WINDOW", "junk")
    assert rpc.pull_window() == 4


# ---------------------------------------------------------------------------
# Wire error handling
# ---------------------------------------------------------------------------

def test_empty_chunk_reply_raises():
    size = 2 * CHUNK
    srv, host = _serve(make_payload(size))
    host.empty_after = 1
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    try:
        with pytest.raises(rpc.RpcError, match="no longer serves"):
            rpc.pull_object_chunked(client, "aa" * 14, size, CHUNK,
                                    window=4)
    finally:
        client.close()
        srv.stop()


def test_short_chunk_reply_raises():
    size = 2 * CHUNK
    srv, host = _serve(make_payload(size))
    host.short_after = 1
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    try:
        with pytest.raises(rpc.RpcError, match="bytes for a"):
            rpc.pull_object_chunked(client, "bb" * 14, size, CHUNK,
                                    window=4)
    finally:
        client.close()
        srv.stop()


def test_handler_error_propagates_and_client_survives():
    """A failed windowed pull discards its outstanding requests; the
    same client then completes a fresh pull (late responses must not
    poison the request-id multiplexing)."""
    size = 4 * CHUNK
    payload = make_payload(size)
    srv, host = _serve(payload)
    host.fail_after = 1
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    try:
        with pytest.raises(Exception):
            rpc.pull_object_chunked(client, "cc" * 14, size, CHUNK,
                                    window=4)
        host.fail_after = None
        got = rpc.pull_object_chunked(client, "cc" * 14, size, CHUNK,
                                      window=4)
        assert got == payload
        assert not client._pending and not client._results
    finally:
        client.close()
        srv.stop()


# ---------------------------------------------------------------------------
# Single-flight dedup (PullManager)
# ---------------------------------------------------------------------------

def test_pull_manager_coalesces_concurrent_pulls():
    pm = object_plane.PullManager()
    calls = []
    gate = threading.Event()

    def fetch():
        calls.append(1)
        gate.wait(5.0)
        return b"the-bytes"

    results, errors = [], []
    barrier = threading.Barrier(8)

    def consumer():
        barrier.wait(timeout=10.0)
        try:
            results.append(pm.pull("o1", fetch))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=consumer) for _ in range(8)]
    for t in threads:
        t.start()
    # Let every waiter join the in-flight entry before the leader lands.
    time.sleep(0.3)
    gate.set()
    for t in threads:
        t.join(timeout=10.0)
    assert not errors
    assert len(calls) == 1, "dedup must perform exactly one pull"
    assert results == [b"the-bytes"] * 8
    assert pm.inflight() == 0


def test_pull_manager_error_reaches_all_waiters_then_retries():
    pm = object_plane.PullManager()
    calls = []
    gate = threading.Event()

    def fetch_fail():
        calls.append(1)
        gate.wait(5.0)
        raise RuntimeError("pull blew up")

    errors = []
    barrier = threading.Barrier(6)

    def consumer():
        barrier.wait(timeout=10.0)
        try:
            pm.pull("o2", fetch_fail)
        except RuntimeError as e:
            errors.append(str(e))

    threads = [threading.Thread(target=consumer) for _ in range(6)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    gate.set()
    for t in threads:
        t.join(timeout=10.0)
    assert errors == ["pull blew up"] * 6
    # The entry was cleared: a retry starts a FRESH pull.
    assert pm.pull("o2", lambda: b"recovered") == b"recovered"
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# Direct-into-arena pulls (pull_into_store) + chaos
# ---------------------------------------------------------------------------

@pytest.fixture
def store(tmp_path):
    yield ShmObjectStore(f"objplane{os.getpid()}", str(tmp_path),
                         capacity=256 << 20)


def test_pull_into_store_caches_sealed_replica(store):
    size = 3 * CHUNK - 17
    payload = make_payload(size)
    srv, host = _serve(payload)
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    oid = ObjectID.from_random()
    try:
        data, cached = object_plane.pull_into_store(
            client, store, oid.hex(), size, CHUNK, window=4)
        assert cached is True
        assert bytes(data) == payload
        assert store.contains(oid)
        # Later readers attach the sealed segment without the wire.
        seg = store.attach(oid, size)
        assert bytes(seg.buf[:size]) == payload
    finally:
        client.close()
        srv.stop()


def test_peer_death_mid_pull_reaps_partial_segment(store):
    """Chaos: the serving peer dies mid-windowed-pull.  The partial
    arena segment must be reaped (no half-written object left for
    attach to find) and a retry against a live peer succeeds."""
    size = 4 * CHUNK
    payload = make_payload(size)
    srv, host = _serve(payload)
    host.die_after = 1
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    oid = ObjectID.from_random()
    try:
        with pytest.raises(Exception):
            object_plane.pull_into_store(
                client, store, oid.hex(), size, CHUNK, window=4,
                timeout=10.0)
        assert not store.contains(oid), \
            "partial segment must not survive a failed pull"
    finally:
        client.close()
        srv.stop()
    # Retry from a healthy peer (the directory would re-resolve the
    # location): pull completes and caches.
    srv2, _ = _serve(payload)
    client2 = rpc.Client(f"127.0.0.1:{srv2.port}")
    try:
        data, cached = object_plane.pull_into_store(
            client2, store, oid.hex(), size, CHUNK, window=4)
        assert bytes(data) == payload
        assert cached and store.contains(oid)
    finally:
        client2.close()
        srv2.stop()


def test_dedup_fan_in_one_wire_pull(store):
    """8 concurrent consumers of one remote object perform exactly one
    wire pull between them (PullManager + pull_into_store end to end)."""
    size = 2 * CHUNK
    payload = make_payload(size)
    srv, host = _serve(payload)
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    oid = ObjectID.from_random()
    pm = object_plane.PullManager()
    results, errors = [], []
    barrier = threading.Barrier(8)

    def consumer():
        barrier.wait(timeout=10.0)
        try:
            data, _ = pm.pull(oid.hex(), lambda: object_plane.pull_into_store(
                client, store, oid.hex(), size, CHUNK, window=4))
            results.append(bytes(data))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=consumer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    try:
        assert not errors
        assert results == [payload] * 8
        # One wire pull: exactly ceil(size/chunk) fetch_chunk requests.
        assert len(host.requests) == -(-size // CHUNK)
    finally:
        client.close()
        srv.stop()


def test_arena_cache_failure_warns_once_per_cause(store, caplog):
    """The old bare `except: pass` is gone: a store that cannot cache
    logs a rate-limited warning and the pull still succeeds uncached."""
    size = CHUNK
    payload = make_payload(size)
    srv, _ = _serve(payload)
    client = rpc.Client(f"127.0.0.1:{srv.port}")

    class _BrokenStore:
        def create(self, oid, size):
            raise MemoryError("arena full (test)")

    from ray_tpu.core import log_once
    log_once.reset()
    try:
        with caplog.at_level("WARNING", logger="ray_tpu.core.object_plane"):
            for hex_ in ("11" * 14, "22" * 14):
                data, cached = object_plane.pull_into_store(
                    client, _BrokenStore(), hex_, size, CHUNK, window=2)
                assert bytes(data) == payload
                assert cached is False
        warnings = [r for r in caplog.records
                    if "could not cache pulled object" in r.message]
        assert len(warnings) == 1, "same cause must be rate-limited"
    finally:
        client.close()
        srv.stop()


# ---------------------------------------------------------------------------
# Locality-aware placement (_pick_node -> _pick_node_indexed)
# ---------------------------------------------------------------------------

class _FakeHead:
    """Just enough ControlServer surface to drive _pick_node over a
    real _NodeIndex: the picker every head runs."""

    _utilization = gcs.ControlServer._utilization
    _locality_bytes = gcs.ControlServer._locality_bytes
    _pick_node = gcs.ControlServer._pick_node
    _pick_node_indexed = gcs.ControlServer._pick_node_indexed

    def __init__(self, nodes, objects, fillers=16):
        # The index answers an ask for a resource that at most 16 nodes
        # have free from that free set alone (least utilized fit, no
        # locality, no packing); idle filler nodes put CPU past that so
        # the hybrid policy and its locality consult are what runs.
        self.nodes = dict(nodes)
        for i in range(fillers):
            self.nodes[f"f{i:02d}"] = _node(f"f{i:02d}")
        self.objects = objects
        self.placement_groups = {}
        self._m_locality_hits = None
        self._node_index = gcs._NodeIndex(self)
        self._node_index.rebuild()

    def _charge_avail(self, charge):
        return self.nodes[charge[1]].available


class _Spec:
    placement_group_hex = ""
    scheduling_strategy = None

    def __init__(self, arg_hexes):
        self.args = [TaskArg(is_ref=True, object_hex=h)
                     for h in arg_hexes]


def _node(nid, cpus=4.0, avail=None, is_head=False):
    return NodeState(node_id=nid, total=ResourceSet({"CPU": cpus}),
                     available=ResourceSet({"CPU": avail if avail is not None
                                            else cpus}),
                     is_head=is_head)


def test_locality_breaks_utilization_ties():
    obj = "ab" * 14
    head = _FakeHead(
        nodes={"head": _node("head", is_head=True), "n2": _node("n2")},
        objects={obj: ObjectEntry(state=READY, size=64 << 20, in_shm=True,
                                  node_id="n2")})
    need = ResourceSet({"CPU": 1.0})
    # Every node idle.  With a 64 MiB arg resident on n2, n2 it is.
    nid, _ = head._pick_node(need, _Spec([obj]))
    assert nid == "n2"
    # No ref args -> the pack walk's first fit in the bucket: the head,
    # which joined first.
    nid, _ = head._pick_node(need, _Spec([]))
    assert nid == "head"


def test_locality_counts_replicas_and_respects_feasibility():
    a, b = "aa" * 14, "bb" * 14
    head = _FakeHead(
        nodes={"head": _node("head", is_head=True),
               "n2": _node("n2", avail=0.5),  # infeasible for 1 CPU
               "n3": _node("n3")},
        objects={a: ObjectEntry(state=READY, size=32 << 20, in_shm=True,
                                node_id="n2", replicas={"n3"}),
                 b: ObjectEntry(state=READY, size=1 << 20, in_shm=True,
                                node_id="head")})
    loc = head._locality_bytes(_Spec([a, b]))
    assert loc == {"n2": 32 << 20, "n3": 32 << 20, "head": 1 << 20}
    # n2 holds the most bytes but lacks CPU: feasibility dominates, the
    # replica holder n3 wins over the head's 1 MiB.
    nid, _ = head._pick_node(ResourceSet({"CPU": 1.0}), _Spec([a, b]))
    assert nid == "n3"


@pytest.mark.parametrize("holder_avail,want", [
    # The holder is the LESS utilized of the two busy nodes: packing
    # alone would take the head (0.25); a fitting holder below the
    # spread threshold wins outright, not only on a utilization tie.
    (4.0, "n2"),
    # The holder fits but sits at 0.75, over the threshold: locality
    # does not pull work onto a node the policy is spreading away from,
    # and the pack walk takes the busiest node under it.
    (1.0, "head"),
])
def test_locality_holder_wins_outright_only_below_threshold(
        holder_avail, want):
    obj = "ac" * 14
    head = _FakeHead(
        nodes={"head": _node("head", avail=3.0, is_head=True),
               "n2": _node("n2", avail=holder_avail)},
        objects={obj: ObjectEntry(state=READY, size=64 << 20, in_shm=True,
                                  node_id="n2")})
    nid, _ = head._pick_node(ResourceSet({"CPU": 1.0}), _Spec([obj]))
    assert nid == want


def test_few_free_nodes_take_the_free_set_not_locality():
    """What a head of up to 16 nodes does today (ROADMAP D4): an ask
    naming a resource goes to that resource's free set, which picks the
    least utilized fit and consults neither locality nor the pack
    threshold."""
    obj = "ad" * 14
    head = _FakeHead(
        nodes={"head": _node("head", is_head=True),
               "n2": _node("n2", avail=3.0)},
        objects={obj: ObjectEntry(state=READY, size=64 << 20, in_shm=True,
                                  node_id="n2")},
        fillers=0)
    nid, _ = head._pick_node(ResourceSet({"CPU": 1.0}), _Spec([obj]))
    assert nid == "head"
    # An ask that names no resource has no free set to go to.
    nid, _ = head._pick_node(ResourceSet({}), _Spec([obj]))
    assert nid == "n2"


def test_pending_and_inline_args_contribute_no_locality():
    head = _FakeHead(
        nodes={"head": _node("head", is_head=True)},
        objects={"ee" * 14: ObjectEntry(state="PENDING", size=1 << 30,
                                        in_shm=True, node_id="n9"),
                 "ff" * 14: ObjectEntry(state=READY, size=1 << 30,
                                        in_shm=False, node_id="n9")})
    spec = _Spec(["ee" * 14, "ff" * 14, "00" * 14])
    spec.args.append(TaskArg(is_ref=False, data=b"inline"))
    assert head._locality_bytes(spec) == {}


# ---------------------------------------------------------------------------
# Metrics + flight recorder plumbing
# ---------------------------------------------------------------------------

def test_object_metric_snapshots_shape_and_counts(store):
    size = CHUNK
    payload = make_payload(size)
    srv, _ = _serve(payload)
    client = rpc.Client(f"127.0.0.1:{srv.port}")
    oid = ObjectID.from_random()
    before = {s["name"]: s for s in object_plane.object_metric_snapshots()}
    try:
        from ray_tpu.util import flight_recorder
        flight_recorder.clear()
        object_plane.pull_into_store(client, store, oid.hex(), size,
                                     CHUNK, window=4)
    finally:
        client.close()
        srv.stop()
    after = {s["name"]: s for s in object_plane.object_metric_snapshots()}
    pulled = (("direction", "pulled"),)
    assert (after["object_transfer_bytes_total"]["series"][pulled]
            - before["object_transfer_bytes_total"]["series"][pulled]) == size
    started = (("result", "started"),)
    assert (after["object_pulls_total"]["series"][started]
            - before["object_pulls_total"]["series"][started]) == 1
    # Flight recorder got the transfer begin/end pair with peer + bytes.
    from ray_tpu.util import flight_recorder
    events = [e for e in flight_recorder.dump()
              if e["category"] == "object"]
    kinds = [e["event"] for e in events]
    assert "pull_begin" in kinds and "pull_end" in kinds
    end = next(e for e in events if e["event"] == "pull_end")
    assert end["bytes"] == size and end["ok"] and "duration_s" in end
    # The snapshots ride the standard local exposition pipeline.
    from ray_tpu.util import metrics as metrics_mod
    names = {s["name"] for s in metrics_mod.local_snapshots()}
    assert "object_transfer_bytes_total" in names
