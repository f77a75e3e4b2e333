"""Control-plane scale probe: locate the head's ceiling on one host.

The biggest cluster any other test exercises is 3 logical nodes;
BASELINE.md's envelope rows are 2,000 nodes / 40k actors / 1M
queued tasks / 1k PGs (on 64-core cloud hosts).  This probe drives the
same four dimensions as far as one host allows and records the rates:

  - logical nodes registered (default 50)
  - queued no-op tasks drained through the scheduler (default 10k)
  - actors created to ALIVE (default 1000 — each actor is a real
    process, so on small hosts the bound is process spawn, not the
    head; the probe records both the rate and that attribution)
  - placement groups created+removed (default 100)

Writes its readings to --out.
Usage: python scripts/scale_probe.py --out FILE [--nodes N] [--tasks N]
       [--actors N] [--pgs N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=50)
    ap.add_argument("--tasks", type=int, default=10_000)
    ap.add_argument("--actors", type=int, default=1_000)
    ap.add_argument("--pgs", type=int, default=100)
    ap.add_argument("--real-nodes", type=int, default=0,
                    help="also join N REAL node-manager processes so the "
                         "head's resource-view sync (N8) is actively "
                         "broadcasting the full node table while the "
                         "logical nodes churn; the probe records the "
                         "view size a manager serves back")
    ap.add_argument("--big-object-gb", type=float, default=0,
                    help="also put+get one N-GiB object through the shm "
                         "arena (BASELINE.md 'max ray.get numpy object' "
                         "row); sizes the arena to fit")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    results: dict = {
        "host_cpus": len(os.sched_getaffinity(0)),
        "targets": {"nodes": args.nodes, "tasks": args.tasks,
                    "actors": args.actors, "pgs": args.pgs},
    }

    # max_workers_per_node clamped so 50 nodes x 64 logical CPUs don't
    # spawn thousands of real worker processes on the probe host; the
    # head's bookkeeping still sees the full logical resource pool.
    sysconf: dict = {"max_workers_per_node": 2}
    if args.big_object_gb:
        # Arena sized to hold the object with headroom; spilling off so
        # the measurement is the shm path, not disk.
        sysconf["object_store_memory"] = int(
            args.big_object_gb * (1 << 30) * 1.25)
        sysconf["object_spilling_threshold"] = 0
    cluster = Cluster(head_node_args={
        "num_cpus": 64, "log_to_driver": False,
        "_system_config": sysconf})

    # -- 0. real node managers (resource-view sync receivers) -------------
    real_procs = []
    try:
        return _probe(args, results, cluster, real_procs)
    finally:
        for p_ in real_procs:
            if p_.poll() is None:
                p_.terminate()


def _probe(args, results, cluster, real_procs) -> int:
    import ray_tpu
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    if args.real_nodes:
        import subprocess

        rt = cluster.runtime
        for i in range(args.real_nodes):
            real_procs.append(subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.core.node_manager",
                 "--address", rt.address, "--node-id", f"real-{i}",
                 "--num-cpus", "2", "--num-tpus", "0"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.time() + 60
        want = {f"real-{i}" for i in range(args.real_nodes)}
        while time.time() < deadline:
            alive = {n["node_id"] for n in cluster.list_nodes()
                     if n["alive"]}
            if want <= alive:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("real node managers failed to join")

    # -- 1. logical nodes --------------------------------------------------
    t0 = time.perf_counter()
    for i in range(args.nodes - 1):
        cluster.add_node(num_cpus=64, node_id=f"scale-{i}")
    dt = time.perf_counter() - t0
    n_nodes = len(cluster.list_nodes())
    results["nodes"] = {"count": n_nodes,
                        "register_per_s": round((args.nodes - 1) / dt, 1)}
    print(f"nodes: {n_nodes} registered at "
          f"{results['nodes']['register_per_s']}/s", flush=True)

    if args.real_nodes:
        # Prove the synced view propagated the FULL node table to a
        # real manager (debounced broadcast, gcs _sync_resource_view):
        # ask the manager's own server for its cluster view.
        from ray_tpu.core import rpc as _rpc

        mgr_addr = next(n["address"] for n in cluster.list_nodes()
                        if n["node_id"] == "real-0")
        view = None
        deadline = time.time() + 30
        while time.time() < deadline:
            conn = _rpc.Client(mgr_addr, connect_timeout=5.0)
            view = conn.call({"op": "cluster_view"}, timeout=10.0)
            conn.close()
            if view and len(view.get("nodes", view)) >= n_nodes:
                break
            time.sleep(0.5)
        nodes_in_view = len(view.get("nodes", view)) if view else 0
        results["resource_view_sync"] = {
            "receivers": args.real_nodes,
            "nodes_in_synced_view": nodes_in_view,
            "full_table": nodes_in_view >= n_nodes,
        }
        print(f"view sync: manager serves {nodes_in_view} nodes "
              f"(full={results['resource_view_sync']['full_table']})",
              flush=True)

    # -- 2. queued tasks ---------------------------------------------------
    @ray_tpu.remote(num_cpus=1)
    def noop():
        return 0

    t0 = time.perf_counter()
    refs = [noop.remote() for _ in range(args.tasks)]
    submit_dt = time.perf_counter() - t0
    ray_tpu.get(refs, timeout=3600)
    drain_dt = time.perf_counter() - t0
    results["tasks"] = {
        "queued": args.tasks,
        "submit_per_s": round(args.tasks / submit_dt, 1),
        "drain_per_s": round(args.tasks / drain_dt, 1),
    }
    print(f"tasks: {args.tasks} submitted at "
          f"{results['tasks']['submit_per_s']}/s, drained at "
          f"{results['tasks']['drain_per_s']}/s", flush=True)

    # -- 3. actors ---------------------------------------------------------
    class A:
        def ping(self):
            return 0

    Actor = ray_tpu.remote(A)
    t0 = time.perf_counter()
    actors = [Actor.options(num_cpus=0.01).remote()
              for _ in range(args.actors)]
    # One call per actor proves every one reached ALIVE and answers.
    ray_tpu.get([a.ping.remote() for a in actors], timeout=3600)
    dt = time.perf_counter() - t0
    results["actors"] = {
        "count": args.actors,
        "to_alive_per_s": round(args.actors / dt, 1),
        "note": "each actor is a dedicated OS process; on few-core "
                "hosts this rate is process-spawn-bound, not "
                "head-bound",
    }
    print(f"actors: {args.actors} alive at "
          f"{results['actors']['to_alive_per_s']}/s", flush=True)

    # Tear the actors down so PG timing below is clean.
    t0 = time.perf_counter()
    for a in actors:
        ray_tpu.kill(a)
    results["actors"]["kill_per_s"] = round(
        args.actors / (time.perf_counter() - t0), 1)

    # -- 4. placement groups ----------------------------------------------
    t0 = time.perf_counter()
    pgs = [placement_group([{"CPU": 1}] * 2, strategy="SPREAD")
           for _ in range(args.pgs)]
    ray_tpu.get([pg.ready() for pg in pgs], timeout=600)
    create_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for pg in pgs:
        remove_placement_group(pg)
    remove_dt = time.perf_counter() - t0
    results["placement_groups"] = {
        "count": args.pgs,
        "create_ready_per_s": round(args.pgs / create_dt, 1),
        "remove_per_s": round(args.pgs / remove_dt, 1),
    }
    print(f"pgs: {args.pgs} ready at "
          f"{results['placement_groups']['create_ready_per_s']}/s, "
          f"removed at {results['placement_groups']['remove_per_s']}/s",
          flush=True)

    # -- 5. large single object (opt-in) ----------------------------------
    if args.big_object_gb:
        import mmap

        import numpy as np

        n = int(args.big_object_gb * (1 << 30) // 8)
        arr = np.arange(n, dtype=np.int64)  # real bytes, not COW zeros
        nbytes = n * 8
        # Control: a bare tmpfs mmap write of the SAME byte count —
        # big-object puts are first-touch page-fault bound on virtualized
        # hosts, so the honest framework number is overhead OVER this.
        ctl_path = os.path.join("/dev/shm", f"scale-probe-ctl-{os.getpid()}")
        with open(ctl_path, "w+b") as f:
            f.truncate(nbytes)
            mm = mmap.mmap(f.fileno(), nbytes)
            view = memoryview(mm)
            t0 = time.perf_counter()
            view[:nbytes] = memoryview(arr).cast("B")
            raw_dt = time.perf_counter() - t0
            view.release()
            mm.close()
        os.unlink(ctl_path)
        t0 = time.perf_counter()
        ref = ray_tpu.put(arr)
        put_dt = time.perf_counter() - t0
        del arr
        t0 = time.perf_counter()
        back = ray_tpu.get(ref, timeout=3600)
        get_dt = time.perf_counter() - t0
        assert int(back[0]) == 0 and int(back[-1]) == n - 1
        gb = nbytes / 1e9
        results["large_object"] = {
            "gigabytes": round(gb, 2),
            "put_s": round(put_dt, 2),
            "put_gb_per_s": round(gb / put_dt, 2),
            "raw_tmpfs_write_s": round(raw_dt, 2),
            "framework_overhead_pct": round(
                max(0.0, put_dt / raw_dt - 1.0) * 100, 1),
            "get_s": round(get_dt, 3),
            "note": "get is a zero-copy view over the shm arena "
                    "(deserialize aliases the segment); "
                    "raw_tmpfs_write_s is a bare mmap write of the same "
                    "byte count on the same host, measured just before "
                    "the put",
        }
        print(f"large object: {gb:.1f} GB put in {put_dt:.1f}s "
              f"(raw tmpfs control {raw_dt:.1f}s -> "
              f"{results['large_object']['framework_overhead_pct']}% "
              f"overhead), get in {get_dt:.3f}s", flush=True)
        del back, ref

    cluster.shutdown()
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
