"""C++ frontend tests (SURVEY.md §2.1 N17 counterpart): the JSON frame
protocol, named-function registration, and the real compiled C++ client
end to end."""

import json
import shutil
import time
import subprocess
import sys

import pytest

import ray_tpu

_REPO_ROOT = str(__import__("pathlib").Path(__file__).resolve().parent.parent)

_BIN = "/tmp/ray_tpu_cpp_example"


def _poll(cluster, obj_hex, timeout=30.0):
    """Poll get_object_json until it leaves 'pending' (what the C++
    client's GetBlocking does on the wire)."""
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        st = cluster.kv().call({"op": "get_object_json", "obj": obj_hex})
        if st["status"] != "pending":
            return st
        time.sleep(0.05)
    return {"status": "pending"}


@pytest.fixture
def cluster():
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def test_json_frame_protocol(cluster):
    """Speak the JSON frame kind directly from Python (what the C++
    client does on the wire)."""
    import socket
    import struct

    host, port = cluster.address.rsplit(":", 1)
    s = socket.create_connection((host, int(port)), timeout=10)
    frame = struct.Struct("<BQI")

    def call(body: dict) -> dict:
        payload = json.dumps(body).encode()
        s.sendall(frame.pack(3, 1, len(payload)) + payload)
        kind, _, length = frame.unpack(_recv(s, frame.size))
        assert kind == 1
        return json.loads(_recv(s, length))

    def _recv(sock, n):
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            assert chunk
            buf += chunk
        return buf

    out = call({"op": "cluster_resources"})
    assert out["status"] == "ok"
    assert out["result"]["CPU"] == 4.0
    out = call({"op": "no_such_op"})
    assert out["status"] == "err"
    s.close()


def test_named_function_python_roundtrip(cluster):
    ray_tpu.register_named_function("mul", lambda a, b: a * b)
    obj = cluster.kv().call({"op": "submit_named_task", "name": "mul",
                             "args": [6, 7]})
    assert _poll(cluster, obj) == {"status": "ready", "value": 42}

    with pytest.raises(Exception, match="no function registered"):
        cluster.kv().call({"op": "submit_named_task", "name": "ghost",
                           "args": []})


def test_poll_that_outruns_the_scheduler_sees_pending(cluster):
    """A named task's return entry is made when its spec drains from
    the head's submit ingress.  A client that polls before the
    scheduler has drained it must read "pending", never "object not
    found" (which the C++ client takes as the task's failure).  The
    head's lock is held here, so the scheduler cannot drain between
    the two calls."""
    ray_tpu.register_named_function("inc", lambda a: a + 1)
    head = cluster.control

    class _Conn:
        meta = {}

    with head.lock:
        obj = head._op_submit_named_task(
            _Conn(), {"name": "inc", "args": [1]})
        st = head._op_get_object_json(_Conn(), {"obj": obj})
    assert st == {"status": "pending"}
    assert _poll(cluster, obj) == {"status": "ready", "value": 2}


def test_non_jsonable_result_reports_clearly(cluster):
    import numpy as np

    ray_tpu.register_named_function("arr", lambda: np.ones(3))
    obj = cluster.kv().call({"op": "submit_named_task", "name": "arr",
                             "args": []})
    st = _poll(cluster, obj)
    assert st["status"] == "error"
    assert "not JSON-representable" in st["error"]


def test_json_frame_hostile_strings(cluster):
    """Failure-mode coverage the round-1 verdict flagged (W7): names,
    keys and values containing quotes/backslashes/newlines/tabs must
    survive the cross-language JSON frames (the C++ header escapes with
    detail::JsonEscape; here we prove the wire handles such strings and
    the function resolves + runs)."""
    import socket
    import struct

    hostile = 'we"ird\\name\nwith\ttabs'
    ray_tpu.register_named_function(hostile, lambda x: x + 1)
    host, port = cluster.address.rsplit(":", 1)
    s = socket.create_connection((host, int(port)), timeout=30)
    frame = struct.Struct("<BQI")

    def recv_exact(n):
        out = b""
        while len(out) < n:
            chunk = s.recv(n - len(out))
            assert chunk, "connection closed"
            out += chunk
        return out

    def call(body: dict) -> dict:
        payload = json.dumps(body).encode()
        s.sendall(frame.pack(3, 9, len(payload)) + payload)
        _, _, ln = frame.unpack(recv_exact(frame.size))
        return json.loads(recv_exact(ln))

    try:
        out = call({"op": "submit_named_task", "name": hostile,
                    "args": [41], "num_cpus": 0.5})
        assert out["status"] == "ok", out
        obj_hex = out["result"]
        # Hostile kv keys/values round-trip too.
        assert call({"op": "kv_put", "key": hostile,
                     "value": hostile})["status"] == "ok"
        got = call({"op": "kv_get", "key": hostile})
        assert got["status"] == "ok" and got["result"] == hostile
        deadline = time.time() + 30
        while time.time() < deadline:
            st = call({"op": "get_object_json", "obj": obj_hex})
            assert st["status"] == "ok", st
            if st["result"]["status"] == "ready":
                assert st["result"]["value"] == 42
                return
            time.sleep(0.1)
        raise AssertionError("result never became ready")
    finally:
        s.close()


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cpp_client_end_to_end(cluster):
    """Compile the real C++ example and run it against the live cluster."""
    build = subprocess.run(
        ["g++", "-std=c++17", "-O1", "-Icpp/include", "cpp/example.cc",
         "-o", _BIN],
        capture_output=True, text=True, cwd=_REPO_ROOT)
    assert build.returncode == 0, build.stderr

    ray_tpu.register_named_function("add", lambda a, b: a + b)
    proc = subprocess.run([_BIN, cluster.address], capture_output=True,
                          text=True, timeout=120)
    assert "CPP_CLIENT_OK" in proc.stdout, (proc.stdout, proc.stderr)


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cpp_shm_zero_copy_read(cluster):
    """The C++ ShmReader maps a driver-put object straight out of the
    node arena (reference plasma C++ client attach path): pin via the
    store library, read zero-copy, checksum must match the serialized
    envelope the driver wrote."""
    import numpy as np

    from ray_tpu.core import serialization

    build = subprocess.run(
        ["g++", "-std=c++17", "-O1", "-Icpp/include", "cpp/shm_example.cc",
         "-o", "/tmp/ray_tpu_shm_example", "-ldl"],
        capture_output=True, text=True, cwd=_REPO_ROOT)
    assert build.returncode == 0, build.stderr

    value = np.arange(300_000, dtype=np.uint8)  # > inline threshold: shm
    ref = ray_tpu.put(value)
    ray_tpu.get(ref)  # ensure sealed + registered

    expected = serialization.serialize(value).to_bytes()
    proc = subprocess.run(
        ["/tmp/ray_tpu_shm_example", cluster.address, ref.hex()],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    size, checksum = map(int, proc.stdout.split())
    assert size == len(expected)
    assert checksum == sum(expected) % (1 << 64)

    # Unmappable objects answer honestly (inline object: not in shm).
    small_ref = ray_tpu.put(b"tiny")
    info = cluster.kv().call({"op": "object_shm_info",
                              "obj": small_ref.hex()})
    assert info == {"in_shm": False}
