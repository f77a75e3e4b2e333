"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that deploys the cell's configuration through the program's
own entry points, warms up every program its traffic can reach, measures
for `--seconds`, checks the outputs, and prints ONE JSON object as the
last line of stdout: correct, attempted, failed, metrics, device (and,
traced, breakdown).  With `--trace 0` the metrics are the cell's
end-to-end metrics; with `--trace 1` its per-layer metrics.

The cell is resolved from BENCHMARK.json to `benchmark/configs/<config>.json`
and `benchmark/traffic/<mix>.json`; the configuration names its driver
under `benchmark/drivers/`, the mix its generator under
`benchmark/generators/`, and every file under `benchmark/layer_metrics/`
is a reader.  A later PR adds files and one `workloads` entry; it edits
nothing here.

`--rehearse` is the CPU dress rehearsal: the configuration's `rehearse`
overrides (tiny sizes), kernels interpreted, no device metric, never
`correct: true`.  Without it a run that finds no TPU exits non-zero and
prints no result; it never falls back.

The run leaves no process behind: it adopts whatever the runtime orphans
and, last at exit, waits until every child has ended.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import atexit  # noqa: E402
import copy  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _adopt_orphans() -> None:
    """Make this process the parent of every process the run leaves
    orphaned (a worker whose node manager has gone), so that it can wait
    for them too."""
    import ctypes

    PR_SET_CHILD_SUBREAPER = 36
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0)


def _children() -> list:
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                # pid (comm) state ppid ...; comm may hold spaces
                if int(f.read().rsplit(")", 1)[1].split()[1]) == me:
                    out.append(int(pid))
        except (OSError, ValueError, IndexError):
            pass
    return out


def _wait_for_children(grace_s: float = 20.0) -> None:
    """Runs last at exit: the run stops every process it started AND waits
    until each has ended.  The program's shutdown kills a worker that is
    slow to go (a TPU client takes seconds to close) without waiting for
    it; left alone it would outlive this process."""
    t0, reaped = time.time(), 0
    deadline, killed = t0 + grace_s, False
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:           # none is left
            # stderr: the last line of stdout is the result
            print(f"benchmark/run.py: waited {time.time() - t0:.2f} s for "
                  f"{reaped} processes to end" + (", killed the last"
                                                  if killed else ""),
                  file=sys.stderr)
            return
        if pid:
            reaped += 1
            continue
        if time.time() > deadline:
            if killed:
                return
            for child in _children():
                try:
                    os.kill(child, signal.SIGKILL)
                except OSError:
                    pass
            deadline, killed = time.time() + 10.0, True
        time.sleep(0.05)


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def main() -> int:
    from benchmark import harness
    from benchmark.harness import BenchFailure, say

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny size; never correct:true")
    args = ap.parse_args()

    os.chdir(ROOT)
    _adopt_orphans()
    atexit.register(_wait_for_children)     # registered first: runs last
    # Workers import `benchmark.*` (the deployments and the train loop
    # live there) and inherit this environment.
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        print("benchmark/run.py: no program here (ray_tpu/ is missing); "
              "nothing to measure", file=sys.stderr)
        return 2
    bench = harness.load_benchmark()
    resolved = harness.resolve_cell(bench, args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.rehearse:
        # Explicitly a CPU run: workers inherit these.  Never otherwise:
        # a replica would inherit JAX_PLATFORMS=cpu and serve off-chip.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count="
            f"{resolved['cell']['chips']}")
        resolved["config"] = _merge(resolved["config"],
                                    resolved["config"].get("rehearse", {}))
        resolved["mix"] = _merge(resolved["mix"],
                                 resolved["mix"].get("rehearse", {}))

    def on_alarm(signum, frame):
        raise BenchFailure("time limit: the run did not finish")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(int(os.environ.get("BENCH_TIME_LIMIT_S", "1150")))
    driver = harness.load_driver(resolved["config"]["driver"])
    say("start", workload=args.workload, seed=args.seed,
        seconds=args.seconds, trace=args.trace, rehearse=args.rehearse)
    try:
        out = driver.run(resolved, args, T_PROCESS_START)
        import jax._src.xla_bridge as xb

        if xb.backends_are_initialized():
            raise BenchFailure("the driver process initialised a JAX "
                               "backend; it must stay off the chip")
        device = harness.device_record(out["device"],
                                       out["memory_peak_bytes"])
        breakdown = None
        if args.trace:
            from benchmark import trace_reduce

            view = None
            if out.get("trace_file"):
                view = trace_reduce.load_xplane(
                    out["trace_file"], t0_epoch=out.get("trace_t0_epoch"))
                if not view.device_planes():
                    view = None     # a CPU trace: no device to read
            counters = out["counters"]
            metrics = harness.read_layer_metrics(
                resolved, out["spans"], view, counters)
            if view is not None and not args.rehearse:
                busy = view.busy()
                device["busy_s"] = busy["busy_s"]
                device["window_s"] = busy["window_s"]
                breakdown = view.breakdown(
                    resolved["config"].get("trace_host_prefix", "bench:"))
                if device["busy_s"] <= 0:
                    raise BenchFailure("the traced run saw no operation "
                                       "on the device")
            elif not args.rehearse:
                raise BenchFailure("the traced run produced no trace")
        else:
            metrics = {}
            units = {m["name"]: m["unit"] for m in resolved["end_to_end"]}
            for name, unit in units.items():
                v = out["values"].get(name)
                if v is None or not math.isfinite(v):
                    raise BenchFailure(f"end-to-end metric {name} has no "
                                       f"finite value: {v}")
                metrics[name] = {"value": float(v), "unit": unit}
        if args.rehearse:
            # A CPU run gives no device number and is never `correct`.
            say("rehearsal", passed=True,
                metrics={k: v["value"] for k, v in metrics.items()},
                values={k: v for k, v in out["values"].items()})
            print(harness.result_line(
                correct=False, attempted=out["attempted"],
                failed=out["failed"], metrics={},
                device=device))
            return 0
        print(harness.result_line(
            correct=out["correct"], attempted=out["attempted"],
            failed=out["failed"], metrics=metrics, device=device,
            breakdown=breakdown), flush=True)
        return 0
    except Exception as e:  # noqa: BLE001 — reported; the exit code says so
        traceback.print_exc()
        say("failed", error=f"{type(e).__name__}: {e}")
        return 1
    finally:
        signal.alarm(0)


if __name__ == "__main__":
    # sys.exit, not os._exit: the runtime's atexit hooks stop the worker
    # template process it started.
    sys.exit(main())
