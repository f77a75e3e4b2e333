"""Command-line interface.

Capability counterpart of the reference's `ray` CLI
(python/ray/scripts/scripts.py — start :571, stop :1047, status :1993,
job submission CLI in dashboard/modules/job/cli.py, state CLI in
util/state/state_cli.py). Run as ``python -m ray_tpu.scripts.cli`` or
``python -m ray_tpu``.

Commands:
  start --head [--num-cpus N] [--num-tpus N] [--dashboard] [--block]
  stop
  status
  list {tasks|actors|nodes|objects|workers|placement_groups}
  summary {tasks|actors}
  memory
  job submit --working-dir D -- <entrypoint...>
  job {status|logs|stop} <job-id>
  job list
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

from ray_tpu.core.api import _address_file, _state_dir


def _dashboard_file() -> str:
    return os.path.join(_state_dir(), "dashboard_url")


def _client(addr: str = None):
    """Bare control-plane client for read-only commands (no runtime)."""
    from ray_tpu.core import rpc

    if not addr:
        try:
            with open(_address_file()) as f:
                addr = f.read().strip()
        except FileNotFoundError:
            print("no running cluster (did you `ray-tpu start --head`?)",
                  file=sys.stderr)
            sys.exit(1)
    try:
        return rpc.Client(addr)
    except OSError:
        print(f"cluster address file points at {addr} but nothing is "
              "listening; removing stale file", file=sys.stderr)
        os.unlink(_address_file())
        sys.exit(1)


def cmd_start(args):
    import ray_tpu

    if not args.head:
        if not args.address:
            print("pass --head to start a cluster or --address=<head> to "
                  "join one", file=sys.stderr)
            return 1
        return _start_worker_node(args)
    rt = ray_tpu.init(num_cpus=args.num_cpus, num_tpus=args.num_tpus)
    os.makedirs(_state_dir(), exist_ok=True)
    with open(_address_file(), "w") as f:
        f.write(rt.address)
    print(f"ray_tpu head started at {rt.address}")
    print(f"connect with ray_tpu.init(address='auto') or "
          f"address='{rt.address}'")
    if args.dashboard:
        from ray_tpu.dashboard import Dashboard

        dash = Dashboard(rt, port=args.dashboard_port)
        with open(_dashboard_file(), "w") as f:
            f.write(dash.url)
        print(f"dashboard at {dash.url}")
    if args.block:
        stop = []
        signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
        signal.signal(signal.SIGINT, lambda *a: stop.append(1))
        # Until a signal, or until `ray-tpu stop` (shutdown_cluster) has
        # stopped the control server under this process.
        while not stop and not rt.control._stopped.wait(0.2):
            pass
        ray_tpu.shutdown()
    else:
        print("running in background of this process; use --block to wait "
              "(or keep this python process alive)")
        signal.pause()
    return 0


def _start_worker_node(args):
    """Join an existing cluster as a worker node: run the per-node
    manager daemon (reference `ray start --address=<head>` starting a
    raylet, scripts.py:571).  --detach forks the daemon into its own
    session and returns once the node registers — the form the
    autoscaler's SSH updater runs (updater.py)."""
    from ray_tpu.core.node_manager import NodeManager

    address = args.address
    if address == "auto":
        with open(_address_file()) as f:
            address = f.read().strip()
    if getattr(args, "detach", False):
        import subprocess

        argv = [sys.executable, "-m", "ray_tpu.scripts.cli", "start",
                "--address", address]
        if args.node_id:
            argv += ["--node-id", args.node_id]
        if args.num_cpus is not None:
            argv += ["--num-cpus", f"{args.num_cpus:g}"]
        if args.num_tpus is not None:
            argv += ["--num-tpus", f"{args.num_tpus:g}"]
        for kv in (args.label or []):
            argv += ["--label", kv]
        log = open(os.path.join(
            _state_dir(), f"node-{args.node_id or 'worker'}.log"), "ab") \
            if os.path.isdir(_state_dir()) else subprocess.DEVNULL
        proc = subprocess.Popen(argv, start_new_session=True,
                                stdout=log, stderr=subprocess.STDOUT)
        # Confirm the daemon survives its startup window.
        time.sleep(1.0)
        if proc.poll() is not None:
            print(f"node daemon exited rc={proc.returncode}",
                  file=sys.stderr)
            return 1
        print(f"node daemon started (pid {proc.pid})")
        return 0
    labels = dict(kv.split("=", 1) for kv in (args.label or []))
    nm = NodeManager(address, num_cpus=args.num_cpus,
                     num_tpus=args.num_tpus, node_id=args.node_id,
                     labels=labels)
    print(f"node {nm.node_id} joined cluster at {address}")
    print(f"object server at {nm.server.address}; Ctrl-C to leave")
    nm.run_forever()
    return 0


def cmd_stop(args):
    client = _client(getattr(args, "address", "") or None)
    if getattr(args, "node", ""):
        # Targeted removal of one worker node (autoscaler teardown path).
        ok = client.call({"op": "remove_node", "node_id": args.node},
                         timeout=10)
        print(f"node {args.node} removed" if ok else
              f"node {args.node} not found")
        return 0
    try:
        client.call({"op": "shutdown_cluster"}, timeout=5)
    except Exception:
        pass  # server exits mid-reply
    for path in (_address_file(), _dashboard_file()):
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
    print("cluster stopped")
    return 0


def cmd_up(args):
    """Provision head + workers from a YAML cluster config (reference
    `ray up`, autoscaler/_private/commands.py)."""
    from ray_tpu.autoscaler import sdk

    config = sdk.load_config(args.config)
    report = sdk.create_or_update_cluster(config)
    print(f"head: {report['head']}")
    for w in report["workers"]:
        print(f"worker {w['node_id']}: {w['status']}")
    for w in report["failed"]:
        print(f"worker {w['node_id']} FAILED: {w['status']} "
              f"{w['error']}", file=sys.stderr)
    return 1 if report["failed"] else 0


def cmd_down(args):
    from ray_tpu.autoscaler import sdk

    config = sdk.load_config(args.config)
    sdk.teardown_cluster(config)
    print("cluster torn down")
    return 0


def _fmt_table(rows, columns):
    if not rows:
        print("(none)")
        return
    widths = {c: max(len(c), *(len(str(r.get(c, ""))) for r in rows))
              for c in columns}
    print("  ".join(c.ljust(widths[c]) for c in columns))
    print("  ".join("-" * widths[c] for c in columns))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(widths[c])
                        for c in columns))


def cmd_status(args):
    client = _client()
    total = client.call({"op": "cluster_resources"})
    avail = client.call({"op": "available_resources"})
    nodes = client.call({"op": "list_nodes"})
    alive = [n for n in nodes if n["alive"]]
    print(f"nodes: {len(alive)} alive / {len(nodes)} total")
    print("resources:")
    for k in sorted(total):
        print(f"  {avail.get(k, 0.0):g}/{total[k]:g} {k}")
    load = client.call({"op": "get_load"})
    if load["demands"]:
        print(f"pending demands: {len(load['demands'])}")
    if load["pg_demands"]:
        print(f"pending placement groups: {len(load['pg_demands'])}")
    return 0


_LIST_COLUMNS = {
    "tasks": ["task_id", "name", "state", "duration_s"],
    "actors": ["actor_id", "class", "name", "state", "pid"],
    "nodes": ["node_id", "alive", "is_head", "resources"],
    "objects": ["object_id", "state", "size", "refcount", "in_shm"],
    "workers": ["worker_id", "kind", "state", "pid"],
    "placement_groups": ["pg_hex", "strategy", "state", "name"],
}


def cmd_list(args):
    client = _client()
    rows = client.call({"op": f"list_{args.kind}"})
    if args.format == "json":
        print(json.dumps(rows, default=str, indent=2))
    else:
        _fmt_table(rows, _LIST_COLUMNS[args.kind])
    return 0


def cmd_summary(args):
    client = _client()
    rows = client.call({"op": f"list_{args.kind}"})
    from collections import Counter

    by_state = Counter(r.get("state", "?") for r in rows)
    print(f"{args.kind}: {len(rows)} total")
    for state, n in sorted(by_state.items()):
        print(f"  {state}: {n}")
    return 0


def cmd_stack(args):
    """Dump every live worker's Python stacks (reference `ray stack`,
    py-spy based; here workers self-report via the profile op)."""
    client = _client()
    workers = client.call({"op": "list_workers"})
    shown = 0
    for w in workers:
        if w.get("state") == "dead" or not w.get("worker_id"):
            continue
        if args.worker and not w["worker_id"].startswith(args.worker):
            continue
        try:
            dump = client.call({"op": "profile_worker",
                                "worker_hex": w["worker_id"],
                                "kind": "stack", "timeout_s": 10})
        except Exception as e:  # noqa: BLE001
            dump = f"<unavailable: {e}>"
        print(f"===== worker {w['worker_id'][:12]} "
              f"(pid {w.get('pid')}, {w.get('kind')}, "
              f"{w.get('state')}) =====")
        print(dump)
        shown += 1
    if not shown:
        print("no live workers matched")
    return 0


def cmd_memory(args):
    client = _client()
    rows = client.call({"op": "list_objects"})
    total = sum(r["size"] or 0 for r in rows)
    in_shm = sum(r["size"] or 0 for r in rows if r["in_shm"])
    print(f"objects: {len(rows)}, {total} bytes total, {in_shm} in shm")
    _fmt_table(sorted(rows, key=lambda r: -(r["size"] or 0))[:20],
               _LIST_COLUMNS["objects"])
    return 0


def cmd_microbenchmark(args):
    """Run the core microbenchmark suite (reference: `ray
    microbenchmark`, _private/ray_perf.py)."""
    from ray_tpu.scripts.microbenchmark import main as run_bench

    return run_bench()


def cmd_timeline(args):
    """Dump the cluster task timeline as chrome-trace JSON (reference:
    `ray timeline`, _private/state.py:434)."""
    client = _client()

    class _Shim:
        def state_list(self, kind):
            return client.call({"op": f"list_{kind}"})

    from ray_tpu.util.timeline import timeline_events

    events = timeline_events(_Shim())
    path = args.output or "timeline.json"
    with open(path, "w") as f:
        json.dump(events, f)
    print(f"wrote {len(events)} events to {path} "
          "(open in chrome://tracing or ui.perfetto.dev)")
    return 0


def cmd_job(args):
    import ray_tpu
    from ray_tpu.job import JobSubmissionClient

    ray_tpu.init(address="auto")
    client = JobSubmissionClient()
    if args.job_cmd == "submit":
        parts = list(args.entrypoint)
        if parts and parts[0] == "--":
            parts = parts[1:]
        import shlex

        entrypoint = " ".join(shlex.quote(p) for p in parts)
        runtime_env = {}
        if args.working_dir:
            runtime_env["working_dir"] = args.working_dir
        job_id = client.submit_job(entrypoint=entrypoint,
                                   runtime_env=runtime_env)
        print(job_id)
        if args.wait:
            st = client.wait_until_finished(job_id, timeout=args.timeout)
            print(st.value)
            print(client.get_job_logs(job_id), end="")
            return 0 if st.value == "SUCCEEDED" else 1
    elif args.job_cmd == "status":
        print(client.get_job_status(args.job_id).value)
    elif args.job_cmd == "logs":
        print(client.get_job_logs(args.job_id), end="")
    elif args.job_cmd == "stop":
        print(client.stop_job(args.job_id))
    elif args.job_cmd == "list":
        _fmt_table(client.list_jobs(),
                   ["job_id", "status", "entrypoint", "returncode"])
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("ray-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a cluster head or join one")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address", default="",
                    help="head address to join as a worker node "
                         "('auto' reads the local address file)")
    sp.add_argument("--node-id", default="")
    sp.add_argument("--label", action="append", default=[],
                    help="k=v node label (repeatable)")
    sp.add_argument("--num-cpus", type=float, default=None)
    sp.add_argument("--num-tpus", type=float, default=None)
    sp.add_argument("--dashboard", action=argparse.BooleanOptionalAction,
                    default=True)
    sp.add_argument("--dashboard-port", type=int, default=0)
    sp.add_argument("--block", action="store_true")
    sp.add_argument("--detach", action="store_true",
                    help="worker join only: fork the node daemon and "
                         "return (the autoscaler updater's form)")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("stop", help="stop the running cluster")
    sp.add_argument("--node", default="",
                    help="remove just this worker node instead of "
                         "stopping the cluster")
    sp.add_argument("--address", default="",
                    help="head address (default: local address file)")
    sp.set_defaults(fn=cmd_stop)

    sp = sub.add_parser("up", help="provision a cluster from a YAML "
                                   "config (autoscaler sdk)")
    sp.add_argument("config")
    sp.set_defaults(fn=cmd_up)

    sp = sub.add_parser("down", help="tear down a provisioned cluster")
    sp.add_argument("config")
    sp.set_defaults(fn=cmd_down)

    sp = sub.add_parser("status", help="cluster resources + load")
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser("list", help="list cluster entities")
    sp.add_argument("kind", choices=sorted(_LIST_COLUMNS))
    sp.add_argument("--format", choices=["table", "json"], default="table")
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("summary", help="counts by state")
    sp.add_argument("kind", choices=["tasks", "actors"])
    sp.set_defaults(fn=cmd_summary)

    sp = sub.add_parser("microbenchmark",
                        help="core-runtime throughput microbenchmarks")
    sp.set_defaults(fn=cmd_microbenchmark)

    sp = sub.add_parser("timeline", help="dump chrome-trace task timeline")
    sp.add_argument("-o", "--output", default="")
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser("memory", help="object store contents")
    sp.set_defaults(fn=cmd_memory)

    sp = sub.add_parser("stack", help="dump live workers' Python stacks")
    sp.add_argument("worker", nargs="?", default="",
                    help="worker hex prefix filter (default: all)")
    sp.set_defaults(fn=cmd_stack)

    sp = sub.add_parser("job", help="job submission")
    jsub = sp.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--working-dir", default=None)
    j.add_argument("--wait", action="store_true")
    j.add_argument("--timeout", type=float, default=300.0)
    j.add_argument("entrypoint", nargs=argparse.REMAINDER)
    for name in ("status", "logs", "stop"):
        j = jsub.add_parser(name)
        j.add_argument("job_id")
    jsub.add_parser("list")
    sp.set_defaults(fn=cmd_job)

    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args) or 0


if __name__ == "__main__":
    sys.exit(main())
