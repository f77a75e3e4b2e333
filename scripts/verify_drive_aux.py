"""Drive the auxiliary subsystems end-to-end: workflow events (incl.
the dashboard HTTP event provider), the serve frame-protocol ingress,
and on-demand worker profiling (stack + jax trace)."""

import json
import os
import socket
import struct
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # these drives run on the host CPU
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import jax  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import serve, workflow  # noqa: E402


def drive_workflow_events(rt):
    from ray_tpu.dashboard.http_head import Dashboard

    @ray_tpu.remote
    def double(x):
        return 2 * x

    import uuid as _uuid

    dash = Dashboard(rt)
    try:
        # Unique id + key: workflow storage persists across drive runs,
        # and a checkpointed event step would complete instantly.
        key = f"golive-{_uuid.uuid4().hex[:8]}"
        ev = workflow.wait_for_event(workflow.KVEventListener, key,
                                     poll_interval_s=0.05)
        wid = workflow.run_async(double.bind(ev),
                                 workflow_id=f"wf_drive_{key}")
        time.sleep(0.2)
        assert workflow.get_status(wid) == workflow.WorkflowStatus.RUNNING
        req = urllib.request.Request(
            dash.url + f"/api/events/{key}", data=json.dumps(8).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.loads(resp.read())["status"] == "ok"
        assert workflow.get_output(wid, timeout=30) == 16
        print("[1] workflow event via dashboard HTTP provider -> 16")

        # Profiling through the dashboard route too.
        from ray_tpu.state.api import list_workers
        pool = [w for w in list_workers() if w["kind"] == "pool"]
        target = pool[0]["worker_id"] if pool else rt.core.worker_hex
        with urllib.request.urlopen(
                dash.url + f"/api/workers/{target}/profile?kind=stack",
                timeout=30) as resp:
            prof = json.loads(resp.read())
        assert "Thread" in prof["profile"]
        print(f"[2] stack profile of {target[:8]} via dashboard "
              f"({len(prof['profile'])} chars)")
    finally:
        dash.stop()

    from ray_tpu.state.api import profile_worker
    trace_dir = profile_worker(rt.core.worker_hex, kind="jax_trace",
                               duration_s=0.3)
    assert os.path.isdir(trace_dir)
    print(f"[3] jax xplane trace captured -> {trace_dir}")


def drive_tqdm(rt):
    from ray_tpu.experimental import tqdm_ray

    @ray_tpu.remote
    def work():
        from ray_tpu.experimental import tqdm_ray as tr
        bar = tr.tqdm(desc="drive-bar", total=5)
        for _ in range(5):
            bar.update(1)
            bar.refresh()
            time.sleep(0.05)
        return bar.n  # left open: the driver monitor sees it

    ref = work.remote()
    seen = False
    deadline = time.time() + 20
    while not seen and time.time() < deadline:
        seen = any(b.get("desc") == "drive-bar"
                   for b in tqdm_ray.live_bars().values())
        time.sleep(0.05)
    assert ray_tpu.get(ref) == 5 and seen
    print("[3b] tqdm_ray: worker bar visible from the driver")


def drive_frame_ingress():
    @serve.deployment
    class Api:
        def __call__(self, request):
            return {"doubled": request.json() * 2}

    serve.run(Api.bind(), name="api", route_prefix="/api")
    addr = serve.start_frame_ingress()
    host, port = addr.rsplit(":", 1)
    frame = struct.Struct("<BQI")

    def recv(s, n):
        buf = b""
        while len(buf) < n:
            chunk = s.recv(n - len(buf))
            assert chunk
            buf += chunk
        return buf

    deadline = time.time() + 20
    while time.time() < deadline:
        s = socket.create_connection((host, int(port)), timeout=10)
        payload = json.dumps({"op": "serve_request", "route": "/api",
                              "payload": 21}).encode()
        s.sendall(frame.pack(3, 1, len(payload)) + payload)
        _, _, length = frame.unpack(recv(s, frame.size))
        reply = json.loads(recv(s, length))
        s.close()
        if reply.get("status") == "ok":
            break
        time.sleep(0.3)
    assert reply == {"status": "ok", "result": {"doubled": 42}}, reply
    print(f"[4] frame-protocol serve ingress at {addr} -> {reply['result']}")
    serve.shutdown()


def main():
    rt = ray_tpu.init(num_cpus=4)
    # Warm a pool worker so the stack profile has a target.
    @ray_tpu.remote
    def warm():
        return 0
    ray_tpu.get(warm.remote())
    drive_workflow_events(rt)
    drive_tqdm(rt)
    drive_frame_ingress()
    ray_tpu.shutdown()
    print("ALL OK")


if __name__ == "__main__":
    main()
