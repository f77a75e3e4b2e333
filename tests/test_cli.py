"""CLI smoke tests (reference: python/ray/tests/test_cli.py).

Drives `python -m ray_tpu start/status/list/stop` as real subprocesses
against an isolated address file (monkeypatched paths).
"""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.setdefault("RAY_TPU_CHIPS", "none")
    return env


def _run(args, **kw):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli"] + args,
        capture_output=True, text=True, timeout=kw.pop("timeout", 60),
        env=_env(), **kw)


@pytest.fixture
def cluster_head(tmp_path, monkeypatch):
    # The address file lies under the temp dir; five other test files
    # start heads of their own, and under xdist they run beside this
    # one.  A temp dir of its own keeps this head's file this head's.
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    address_file = tmp_path / "ray_tpu" / "cluster_address"
    env = _env()
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "start", "--head",
         "--num-cpus", "2", "--block", "--no-dashboard"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        deadline = time.monotonic() + 60
        while not (address_file.exists() and address_file.read_text()):
            if time.monotonic() > deadline or proc.poll() is not None:
                out = proc.stdout.read() if proc.stdout else ""
                raise RuntimeError(f"head did not start: {out}")
            time.sleep(0.1)
    except BaseException:
        # The pre-yield error path must not leak a --block head: each
        # leaked head idles forever and skews every later timing
        # measurement on the host.
        proc.kill()
        raise
    yield proc
    _run(["stop"])
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_cli_status_and_list(cluster_head):
    out = _run(["status"])
    assert out.returncode == 0, out.stderr
    assert "nodes: 1 alive" in out.stdout
    assert "CPU" in out.stdout

    out = _run(["list", "nodes"])
    assert out.returncode == 0, out.stderr
    assert "head" in out.stdout

    out = _run(["list", "nodes", "--format", "json"])
    assert '"alive": true' in out.stdout


def test_cli_job_submit_wait(cluster_head):
    out = _run(["job", "submit", "--wait", "--",
                sys.executable, "-c", "print('cli job ran')"],
               timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SUCCEEDED" in out.stdout
    assert "cli job ran" in out.stdout


def test_cli_stop_then_status_errors(cluster_head):
    out = _run(["stop"])
    assert "stopped" in out.stdout
    out = _run(["status"])
    assert out.returncode == 1
