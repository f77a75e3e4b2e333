"""Control-plane micro-batching: KIND_BATCH wire frames and the
coalescing send path (core/rpc.py), plus the end-to-end burst-submission
guarantee that frames-sent stays well below messages-sent."""

import json
import pickle
import socket
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import rpc


# ---------------------------------------------------------------------------
# Wire-format round trips (raw sockets: prove the protocol, not the client)
# ---------------------------------------------------------------------------


class _Echo:
    """Handler recording every message; echo/boom for request ops."""

    def __init__(self):
        self.got = []
        self.lock = threading.Lock()

    def __call__(self, conn, msg):
        if msg.get("op") == "echo":
            return msg["x"]
        if msg.get("op") == "boom":
            raise ValueError("boom")
        with self.lock:
            self.got.append(msg)
        return None


@pytest.fixture
def echo_server():
    handler = _Echo()
    srv = rpc.Server(handler)
    yield srv, handler
    srv.stop()


def _raw_conn(srv):
    sock = socket.create_connection(("127.0.0.1", srv.port))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock


def _batch_frame(entries):
    blob = pickle.dumps(entries, protocol=5)
    return rpc._FRAME.pack(rpc.KIND_BATCH, 0, len(blob)) + blob


def test_batch_frame_roundtrip_order(echo_server):
    srv, handler = echo_server
    sock = _raw_conn(srv)
    entries = [(rpc.KIND_ONEWAY, 0,
                pickle.dumps({"op": "note", "i": i})) for i in range(20)]
    entries.append((rpc.KIND_REQUEST, 99,
                    pickle.dumps({"op": "echo", "x": "tail"})))
    sock.sendall(_batch_frame(entries))
    kind, req_id, payload = rpc._recv_frame(sock)
    assert (kind, req_id) == (rpc.KIND_RESPONSE, 99)
    assert pickle.loads(payload) == ("ok", "tail")
    # The response came after every sub-message was dispatched in order.
    assert [m["i"] for m in handler.got] == list(range(20))
    sock.close()


def test_batch_interleaves_with_plain_frames(echo_server):
    srv, handler = echo_server
    sock = _raw_conn(srv)
    rpc._send_frame(sock, rpc.KIND_ONEWAY, 0,
                    pickle.dumps({"op": "note", "i": 0}))
    sock.sendall(_batch_frame(
        [(rpc.KIND_ONEWAY, 0, pickle.dumps({"op": "note", "i": i}))
         for i in (1, 2)]))
    rpc._send_frame(sock, rpc.KIND_ONEWAY, 0,
                    pickle.dumps({"op": "note", "i": 3}))
    # Request frame acts as an ordering barrier (same serve thread).
    rpc._send_frame(sock, rpc.KIND_REQUEST, 7,
                    pickle.dumps({"op": "echo", "x": 1}))
    kind, req_id, payload = rpc._recv_frame(sock)
    assert pickle.loads(payload) == ("ok", 1)
    assert [m["i"] for m in handler.got] == [0, 1, 2, 3]
    sock.close()


def test_json_batch_cross_lang(echo_server):
    """KIND_BATCH_JSON stays representable for the C++ client: plain
    JSON in, one JSON KIND_RESPONSE per sub-request out."""
    srv, _ = echo_server
    sock = _raw_conn(srv)
    doc = json.dumps([
        [rpc.KIND_REQUEST_JSON, 11, {"op": "echo", "x": "a"}],
        [rpc.KIND_REQUEST_JSON, 12, {"op": "echo", "x": "b"}],
    ]).encode()
    sock.sendall(rpc._FRAME.pack(rpc.KIND_BATCH_JSON, 0, len(doc)) + doc)
    for want_id, want_x in ((11, "a"), (12, "b")):
        kind, req_id, payload = rpc._recv_frame(sock)
        assert (kind, req_id) == (rpc.KIND_RESPONSE, want_id)
        assert json.loads(payload) == {"status": "ok", "result": want_x}
    sock.close()


def test_error_propagation_in_batch(echo_server):
    """A failing sub-request responds ("err", e) exactly like a failing
    standalone request; later sub-messages still dispatch."""
    srv, handler = echo_server
    sock = _raw_conn(srv)
    sock.sendall(_batch_frame([
        (rpc.KIND_REQUEST, 21, pickle.dumps({"op": "boom"})),
        (rpc.KIND_ONEWAY, 0, pickle.dumps({"op": "note", "i": 5})),
        (rpc.KIND_REQUEST, 22, pickle.dumps({"op": "echo", "x": "ok"})),
    ]))
    kind, req_id, payload = rpc._recv_frame(sock)
    assert req_id == 21
    status, err = pickle.loads(payload)
    assert status == "err" and isinstance(err, ValueError)
    kind, req_id, payload = rpc._recv_frame(sock)
    assert req_id == 22 and pickle.loads(payload) == ("ok", "ok")
    assert [m["i"] for m in handler.got] == [5]
    sock.close()

    # The same error surfaces as a raised exception through Client.call
    # even when the request rode a coalesced frame.
    cli = rpc.Client(srv.address)
    with pytest.raises(ValueError, match="boom"):
        cli.call({"op": "boom"})
    cli.close()


# ---------------------------------------------------------------------------
# The coalescing sender itself
# ---------------------------------------------------------------------------


class _StubSock:
    """Socket stand-in whose sendall can be gated to simulate a slow
    wire, capturing every frame written."""

    def __init__(self):
        self.frames = []
        self.gate = threading.Event()
        self.gate.set()
        self.sent = threading.Event()

    def sendall(self, data):
        self.frames.append(bytes(data))
        self.sent.set()
        self.gate.wait()


def test_sender_coalesces_while_wire_busy():
    sock = _StubSock()
    sender = rpc._CoalescingSender(sock, threading.Lock())
    sock.gate.clear()
    t = threading.Thread(
        target=sender.send,
        args=(rpc.KIND_ONEWAY, 0, pickle.dumps({"i": 0})))
    t.start()
    assert sock.sent.wait(2.0)  # first message went out immediately
    for i in range(1, 6):
        sender.send(rpc.KIND_ONEWAY, 0, pickle.dumps({"i": i}))
    sock.gate.set()
    t.join(2.0)
    sender.flush()
    # Exactly two frames: the immediate single + ONE batch of the five
    # messages that piled up while the wire was busy.
    assert len(sock.frames) == 2
    kind, _, length = rpc._FRAME.unpack(sock.frames[1][:rpc._FRAME.size])
    assert kind == rpc.KIND_BATCH
    entries = pickle.loads(sock.frames[1][rpc._FRAME.size:])
    assert [pickle.loads(p)["i"] for _, _, p in entries] == [1, 2, 3, 4, 5]
    assert sender.msgs_sent == 6
    assert sender.frames_sent == 2
    assert sender.batches_sent == 1


def test_sender_single_messages_stay_plain_frames():
    """An uncontended link is byte-for-byte the unbatched protocol."""
    sock = _StubSock()
    sender = rpc._CoalescingSender(sock, threading.Lock())
    payloads = [pickle.dumps({"i": i}) for i in range(3)]
    for p in payloads:
        sender.send(rpc.KIND_ONEWAY, 0, p)
    assert sender.batches_sent == 0
    for frame, payload in zip(sock.frames, payloads):
        assert frame == rpc._FRAME.pack(
            rpc.KIND_ONEWAY, 0, len(payload)) + payload


def test_flush_us_knob_parsing(monkeypatch):
    """RAY_TPU_RPC_FLUSH_US: microsecond linger before each coalesced
    flush; 0 (default) keeps first-message latency at zero, garbage and
    negatives fall back to 0."""
    monkeypatch.delenv("RAY_TPU_RPC_FLUSH_US", raising=False)
    assert rpc._flush_us() == 0
    monkeypatch.setenv("RAY_TPU_RPC_FLUSH_US", "250")
    assert rpc._flush_us() == 250
    monkeypatch.setenv("RAY_TPU_RPC_FLUSH_US", "-7")
    assert rpc._flush_us() == 0
    monkeypatch.setenv("RAY_TPU_RPC_FLUSH_US", "bogus")
    assert rpc._flush_us() == 0
    sock = _StubSock()
    monkeypatch.setenv("RAY_TPU_RPC_FLUSH_US", "40000")
    assert rpc._CoalescingSender(sock, threading.Lock()).linger_s \
        == pytest.approx(0.04)


def test_flush_timer_coalesces_trailing_messages(monkeypatch):
    """With a linger window the drainer waits before swapping the
    buffer, so messages sent moments after the first ride the SAME
    frame — a ping-pong burst becomes one KIND_BATCH even on an idle
    wire (where the default would flush each message by itself)."""
    monkeypatch.setenv("RAY_TPU_RPC_FLUSH_US", "200000")  # 200 ms
    sock = _StubSock()
    sender = rpc._CoalescingSender(sock, threading.Lock())
    t = threading.Thread(
        target=sender.send,
        args=(rpc.KIND_ONEWAY, 0, pickle.dumps({"i": 0})))
    t.start()
    deadline = time.monotonic() + 2.0
    while not sender._sending and time.monotonic() < deadline:
        time.sleep(0.001)  # wait for the drainer to claim the flush
    for i in range(1, 5):
        sender.send(rpc.KIND_ONEWAY, 0, pickle.dumps({"i": i}))
    t.join(5.0)
    sender.flush()
    assert sender.msgs_sent == 5
    # All five coalesced into a single batch frame: the linger window
    # held the first flush open while the trailing sends piled in.
    assert len(sock.frames) == 1
    kind, _, _ = rpc._FRAME.unpack(sock.frames[0][:rpc._FRAME.size])
    assert kind == rpc.KIND_BATCH
    entries = pickle.loads(sock.frames[0][rpc._FRAME.size:])
    assert [pickle.loads(p)["i"] for _, _, p in entries] == [0, 1, 2, 3, 4]
    assert sender.batches_sent == 1


def test_flush_fence_skips_linger(monkeypatch):
    """flush() is an ordering fence: it must not sit out the linger
    window (shutdown and oversized-result handoffs want bytes out NOW)."""
    monkeypatch.setenv("RAY_TPU_RPC_FLUSH_US", "400000")  # 400 ms
    sock = _StubSock()
    sender = rpc._CoalescingSender(sock, threading.Lock())
    with sender._lock:  # enqueue without claiming the drainer role
        sender._buf.append((rpc.KIND_ONEWAY, 0, pickle.dumps({"i": 0})))
        sender.msgs_sent += 1
    t0 = time.monotonic()
    sender.flush()
    assert time.monotonic() - t0 < 0.35  # no 400 ms linger on the fence
    assert len(sock.frames) == 1


# ---------------------------------------------------------------------------
# Ref-count delta vectors
# ---------------------------------------------------------------------------


def test_head_frames_merge_refcount_runs():
    from ray_tpu.core.runtime import CoreClient

    items = [("incref", "aa"), ("decref", "aa"), ("incref", "bb"),
             ("decref", "cc"), ("decref", "cc")]
    frames = list(CoreClient._head_frames(items))
    assert len(frames) == 1
    end, msg = frames[0]
    assert end == len(items)
    assert msg == {"op": "refcount_delta",
                   "deltas": {"bb": 1, "cc": -2}}  # "aa" netted to zero

    # A submit in the middle is an ordering barrier: ref runs on either
    # side must not merge across it.
    items = [("incref", "aa"), ("submit", "SPEC"), ("decref", "aa")]
    msgs = [m for _, m in CoreClient._head_frames(items)]
    assert [m["op"] for m in msgs] == ["incref", "submit_task", "decref"]


def test_head_frames_all_zero_net_drops_frame():
    from ray_tpu.core.runtime import CoreClient

    items = [("incref", "aa"), ("decref", "aa")]
    assert list(CoreClient._head_frames(items)) == []


# ---------------------------------------------------------------------------
# End-to-end: burst submission sends fewer frames than tasks
# ---------------------------------------------------------------------------


def _driver_wire_stats(rt):
    clients = [rt.core.client] + list(rt.core._actor_conns.values())
    return (sum(c.frames_sent for c in clients),
            sum(c.msgs_sent for c in clients))


def test_burst_submission_sends_fewer_frames_than_tasks(ray_start_regular):
    rt = ray_start_regular

    @ray_tpu.remote
    def noop(i):
        return i

    # Warm the pool so steady-state traffic (not worker startup) is
    # what gets measured.
    ray_tpu.get([noop.remote(i) for i in range(16)])

    n = 1000
    frames0, msgs0 = _driver_wire_stats(rt)
    refs = [noop.remote(i) for i in range(n)]
    assert ray_tpu.get(refs) == list(range(n))
    frames1, msgs1 = _driver_wire_stats(rt)
    frames, msgs = frames1 - frames0, msgs1 - msgs0
    # ≥1k submissions plus their ref-count/completion traffic must leave
    # the driver in measurably fewer frames than tasks.
    assert frames < n, (frames, msgs)


def test_wait_large_ref_list_batches(ray_start_regular):
    rt = ray_start_regular

    @ray_tpu.remote
    def noop(i):
        return i

    ray_tpu.get([noop.remote(i) for i in range(8)])
    n = 300
    frames0, _ = _driver_wire_stats(rt)
    refs = [noop.remote(i) for i in range(n)]
    not_ready = list(refs)
    while not_ready:
        ready, not_ready = ray_tpu.wait(
            not_ready, num_returns=min(10, len(not_ready)), timeout=10.0)
        assert ready
    frames1, _ = _driver_wire_stats(rt)
    assert frames1 - frames0 < n
    del refs
    time.sleep(0.05)
