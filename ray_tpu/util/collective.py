"""Host-level collective communication groups (p2p ring transport).

Capability counterpart of the reference's ray.util.collective
(python/ray/util/collective/collective.py — GroupManager :40,
init_collective_group :120, declarative create_collective_group :151,
allreduce/allgather/reducescatter/broadcast/send/recv/barrier :258–615).

TPU-native split (SURVEY.md §2.4): the reference's NCCL tier — collectives
*between accelerator buffers* — does not exist on TPU as a separate
runtime: intra-slice collectives compile into the XLA program over the ICI
mesh (jax.lax.psum/all_gather/ppermute inside pjit — see
ray_tpu.parallel). What remains host-side is the DCN/gloo tier: processes
(actors, trainers, env-runners) exchanging host arrays across the cluster.

Transport design (reference analogue: the ring algorithms of
util/collective/collective_group/nccl_collective_group.py, rebuilt on the
framework's own frame protocol): each member runs a small rpc endpoint;
the GCS KV is used ONLY for bootstrap (rank → address rendezvous).  Ops
move bytes directly peer-to-peer:

  - allreduce: bandwidth-optimal ring (reduce-scatter + allgather,
    2·(N-1) steps of 1/N-sized chunks) — O(size) bytes per rank instead
    of the old O(N·size) through the head.
  - allgather / reducescatter: the matching ring phases.
  - broadcast: chain forwarding from the source.
  - send/recv: direct push into the peer's inbox.

Receives block on a condition variable (no sleep-polling in the op
path).
"""

from __future__ import annotations

import threading
import time
from enum import Enum
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.core import rpc
from ray_tpu.core.config import get_config
from ray_tpu.core.ids import ObjectID
from ray_tpu.core.runtime import get_runtime
from ray_tpu.experimental import internal_kv


class ReduceOp(Enum):
    SUM = "sum"
    PRODUCT = "product"
    MIN = "min"
    MAX = "max"


_REDUCERS = {
    ReduceOp.SUM: lambda arrs: np.sum(arrs, axis=0),
    ReduceOp.PRODUCT: lambda arrs: np.prod(arrs, axis=0),
    ReduceOp.MIN: lambda arrs: np.min(arrs, axis=0),
    ReduceOp.MAX: lambda arrs: np.max(arrs, axis=0),
}

_REDUCE2 = {
    ReduceOp.SUM: lambda a, b: a + b,
    ReduceOp.PRODUCT: lambda a, b: a * b,
    ReduceOp.MIN: np.minimum,
    ReduceOp.MAX: np.maximum,
}

_POLL_S = 0.002  # bootstrap-only rendezvous poll
_DEFAULT_TIMEOUT_S = 60.0


class CollectiveGroupError(RuntimeError):
    pass


class _Inbox:
    """Keyed mailbox with blocking take (condition variable, no polling)."""

    def __init__(self):
        self._cv = threading.Condition()
        self._msgs: Dict[tuple, dict] = {}
        self._closed = False

    def put(self, key: tuple, msg: dict):
        with self._cv:
            self._msgs[key] = msg
            self._cv.notify_all()

    def take(self, key: tuple, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        with self._cv:
            while key not in self._msgs:
                if self._closed:
                    raise CollectiveGroupError("collective group destroyed")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise CollectiveGroupError(
                        f"collective op timed out waiting for {key}")
                self._cv.wait(remaining)
            return self._msgs.pop(key)

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()


def _encode(arr: np.ndarray) -> dict:
    arr = np.ascontiguousarray(arr)
    return {"dtype": str(arr.dtype), "shape": arr.shape,
            "data": arr.tobytes()}


def _decode(msg: dict) -> np.ndarray:
    return np.frombuffer(
        msg["data"], dtype=msg["dtype"]).reshape(msg["shape"]).copy()


class HostCollectiveGroup:
    """One process's membership in a named collective group (p2p ring)."""

    def __init__(self, group_name: str, world_size: int, rank: int,
                 timeout_s: float = _DEFAULT_TIMEOUT_S):
        if not (0 <= rank < world_size):
            raise ValueError(f"rank {rank} outside world_size {world_size}")
        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        self.timeout_s = timeout_s
        self._seq: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._inbox = _Inbox()
        self._peers: Dict[int, tuple] = {}  # rank -> (client, store_node)
        cfg = get_config()
        self.server = rpc.Server(self._handle, host=cfg.node_ip_address)
        self.address = f"{cfg.advertised_host()}:{self.server.port}"
        # Same-node shm fast path (the NCCL shared-memory transport
        # analogue): ranks on one host hand payloads through the node's
        # arena — one memcpy in, zero-copy read out — and only the tiny
        # control message rides the socket.  Cross-host ranks fall back
        # to raw bytes on the frame protocol.
        rt = get_runtime()
        self._store = getattr(rt.core, "store", None)
        # Thin clients (store=None) advertise no store node so peers
        # never pick the shm path toward them.
        self._store_node = getattr(rt.core, "store_node", "head") \
            if self._store is not None else ""
        # Bootstrap rendezvous: the ONLY use of the KV in this transport.
        internal_kv.kv_put(self._addr_key(rank),
                           (self.address, self._store_node))

    # -- plumbing --------------------------------------------------------
    def _addr_key(self, rank: int) -> str:
        return f"colp2p/{self.group_name}/{rank}"

    def _handle(self, conn, msg):
        if msg.get("op") == "col_msg":
            self._inbox.put((msg["kind"], msg["seq"], msg["src"]), msg)
            return None
        if msg.get("op") == "ping":
            return "pong"
        raise ValueError(f"unknown collective op {msg.get('op')}")

    def _next_seq(self, kind: str) -> int:
        with self._lock:
            n = self._seq.get(kind, 0)
            self._seq[kind] = n + 1
        return n

    def _peer(self, rank: int) -> tuple:
        with self._lock:
            entry = self._peers.get(rank)
        if entry is not None and not entry[0]._closed:
            return entry
        deadline = time.monotonic() + self.timeout_s
        while True:
            val = internal_kv.kv_get(self._addr_key(rank))
            if val is not None:
                break
            if time.monotonic() > deadline:
                raise CollectiveGroupError(
                    f"rank {rank} of group {self.group_name!r} never "
                    "registered its endpoint")
            time.sleep(_POLL_S)
        addr, store_node = val
        client = rpc.Client(addr, connect_timeout=10.0)
        entry = (client, store_node)
        with self._lock:
            racer = self._peers.get(rank)
            if racer is not None and not racer[0]._closed:
                # Another thread dialed first and its client is live.
                entry = racer
            else:
                self._peers[rank] = entry  # fresh or replacing a dead one
        if entry[0] is not client:
            client.close()
        return entry

    def _msg_oid(self, src: int, dst: int, kind: str, seq) -> ObjectID:
        import hashlib

        h = hashlib.sha1(
            f"colp2p|{self.group_name}|{kind}|{seq}|{src}|{dst}"
            .encode()).digest()
        return ObjectID(h[:14])

    def _send_to(self, dst: int, kind: str, seq, arr: np.ndarray):
        client, peer_node = self._peer(dst)
        arr = np.ascontiguousarray(arr)
        head = {"op": "col_msg", "kind": kind, "seq": seq,
                "src": self.rank, "dtype": str(arr.dtype),
                "shape": arr.shape}
        if self._store is not None and self._store_node \
                and peer_node == self._store_node:
            # Same arena: one memcpy into shm; peer reads zero-copy.
            oid = self._msg_oid(self.rank, dst, kind, seq)
            created = False
            try:
                seg = self._store.create(oid, max(arr.nbytes, 1))
                created = True
                seg.buf[:arr.nbytes] = memoryview(arr).cast("B")
                self._store.seal(oid)
                client.send({**head, "shm": oid.hex(),
                             "nbytes": arr.nbytes})
                return
            except Exception:
                # Arena full/unavailable OR the notify failed: retire any
                # created segment (only the receiver would ever delete it,
                # and it will never hear about this one) and fall back.
                if created:
                    try:
                        self._store.delete(oid)
                    except Exception:
                        pass
        client.send({**head, "data": arr.tobytes()})

    def _recv_from(self, src: int, kind: str, seq) -> np.ndarray:
        try:
            msg = self._inbox.take((kind, seq, src), self.timeout_s)
        except CollectiveGroupError:
            # The sender may have parked a segment for us (same-arena
            # path) before the op died: retire it so timeouts don't
            # strand payload-sized blocks.
            if self._store is not None:
                try:
                    self._store.delete(
                        self._msg_oid(src, self.rank, kind, seq))
                except Exception:
                    pass
            raise
        if "shm" in msg:
            oid = ObjectID.from_hex(msg["shm"])
            seg = self._store.attach(oid, max(msg["nbytes"], 1))
            arr = np.frombuffer(
                seg.buf[:msg["nbytes"]],
                dtype=msg["dtype"]).reshape(msg["shape"]).copy()
            # Single-consumer message: the receiver retires the segment.
            self._store.release(oid)
            self._store.delete(oid)
            return arr
        return _decode(msg)

    # -- collective ops --------------------------------------------------
    def barrier(self):
        self.allgather(np.zeros((), np.uint8))

    def allgather(self, array) -> List[np.ndarray]:
        """Ring allgather: N-1 steps, each forwarding one rank's array."""
        local = np.array(array)
        n = self.world_size
        if n == 1:
            return [local]
        seq = self._next_seq("ag")
        nxt, prv = (self.rank + 1) % n, (self.rank - 1) % n
        parts: List[Optional[np.ndarray]] = [None] * n
        parts[self.rank] = local
        cur = local
        for step in range(n - 1):
            self._send_to(nxt, "ag", (seq, step), cur)
            cur = self._recv_from(prv, "ag", (seq, step))
            parts[(self.rank - step - 1) % n] = cur
        return parts  # type: ignore[return-value]

    def _ring_reduce_scatter(self, chunks: List[np.ndarray], kind: str,
                             seq, op: ReduceOp
                             ) -> Tuple[List[np.ndarray], int]:
        """In-place ring reduce-scatter over pre-split chunks.

        ``kind`` must be unique per calling op (wire keys are
        (kind, seq, src); a shared kind across ops with independent seq
        counters would collide in the inbox).  Uses a virtual rank
        v = rank-1 so the fully reduced chunk each rank ends with is
        chunk[rank] (the natural reducescatter output).  Returns
        (chunks, owned_index)."""
        n = self.world_size
        nxt, prv = (self.rank + 1) % n, (self.rank - 1) % n
        v = (self.rank - 1) % n
        red = _REDUCE2[op]
        for step in range(n - 1):
            send_idx = (v - step) % n
            recv_idx = (v - step - 1) % n
            self._send_to(nxt, kind, (seq, step), chunks[send_idx])
            incoming = self._recv_from(prv, kind, (seq, step))
            chunks[recv_idx] = red(chunks[recv_idx], incoming)
        return chunks, self.rank

    def allreduce(self, array, op: ReduceOp = ReduceOp.SUM) -> np.ndarray:
        """Bandwidth-optimal ring allreduce: reduce-scatter + allgather,
        2·(N-1) steps of 1/N-sized chunks."""
        arr = np.asarray(array)
        n = self.world_size
        if n == 1:
            return arr.copy()
        seq = self._next_seq("ar")
        flat = np.ascontiguousarray(arr).reshape(-1)
        pad = (-len(flat)) % n
        if pad:
            flat = np.concatenate(
                [flat, np.zeros(pad, flat.dtype)])
        chunks = [c.copy() for c in np.split(flat, n)]
        chunks, owned = self._ring_reduce_scatter(chunks, "ar-rs", seq, op)
        # allgather phase: circulate the reduced chunks.
        nxt, prv = (self.rank + 1) % n, (self.rank - 1) % n
        cur_idx = owned
        for step in range(n - 1):
            self._send_to(nxt, "arg", (seq, step), chunks[cur_idx])
            cur_idx = (cur_idx - 1) % n
            chunks[cur_idx] = self._recv_from(prv, "arg", (seq, step))
        out = np.concatenate(chunks)
        if pad:
            out = out[:-pad]
        return out.reshape(arr.shape)

    def reducescatter(self, array, op: ReduceOp = ReduceOp.SUM) -> np.ndarray:
        """Reduce across ranks, then return this rank's 1/world_size shard
        (leading axis must divide evenly) — ONE ring phase, no full
        allreduce."""
        arr = np.asarray(array)
        n = self.world_size
        if arr.shape[0] % n != 0:
            raise ValueError(
                f"leading dim {arr.shape[0]} not divisible by world_size "
                f"{n}")
        if n == 1:
            return arr.copy()
        seq = self._next_seq("rs-op")
        chunks = [c.copy() for c in np.split(np.ascontiguousarray(arr), n)]
        chunks, owned = self._ring_reduce_scatter(chunks, "rs", seq, op)
        return chunks[owned]

    def broadcast(self, array, src_rank: int = 0) -> np.ndarray:
        """Chain forwarding: src → src+1 → ... around the ring."""
        n = self.world_size
        if n == 1:
            return np.array(array)
        seq = self._next_seq("bc")
        nxt, prv = (self.rank + 1) % n, (self.rank - 1) % n
        if self.rank == src_rank:
            out = np.asarray(array)
            if nxt != src_rank:
                self._send_to(nxt, "bc", seq, out)
        else:
            out = self._recv_from(prv, "bc", seq)
            if nxt != src_rank:
                self._send_to(nxt, "bc", seq, out)
        return out

    def send(self, array, dst_rank: int):
        if dst_rank == self.rank:
            raise ValueError("cannot send to self")
        seq = self._next_seq(f"p2p-{self.rank}-{dst_rank}")
        self._send_to(dst_rank, f"p2p-{self.rank}-{dst_rank}", seq,
                      np.asarray(array))

    def recv(self, src_rank: int) -> np.ndarray:
        if src_rank == self.rank:
            raise ValueError("cannot recv from self")
        seq = self._next_seq(f"p2p-{src_rank}-{self.rank}")
        return self._recv_from(src_rank, f"p2p-{src_rank}-{self.rank}", seq)

    def close(self):
        self._inbox.close()
        for client, _node in self._peers.values():
            try:
                client.close()
            except Exception:
                pass
        try:
            self.server.stop()
        except Exception:
            pass
        try:
            internal_kv.kv_del(self._addr_key(self.rank))
        except Exception:
            pass


class GroupManager:
    """Per-process registry of collective groups (reference
    collective.py:40)."""

    def __init__(self):
        self._groups: Dict[str, object] = {}
        self._lock = threading.Lock()

    def create(self, group_name: str, world_size: int, rank: int,
               timeout_s: float = _DEFAULT_TIMEOUT_S):
        with self._lock:
            if group_name in self._groups:
                raise CollectiveGroupError(
                    f"group {group_name!r} already initialized in this "
                    "process")
            g = HostCollectiveGroup(group_name, world_size, rank,
                                    timeout_s)
            self._groups[group_name] = g
            return g

    def get(self, group_name: str):
        with self._lock:
            g = self._groups.get(group_name)
        if g is not None:
            return g
        # Declarative path: the group may have been declared cluster-wide
        # (create_collective_group); resolve this process's rank lazily.
        decl = internal_kv.kv_get(f"col-decl/{group_name}")
        if decl is None:
            return None
        me = _self_actor_hex()
        if me and me in decl["actor_ranks"]:
            return self.create(group_name, decl["world_size"],
                               decl["actor_ranks"][me])
        return None

    def destroy(self, group_name: str):
        with self._lock:
            g = self._groups.pop(group_name, None)
        if g is not None:
            try:
                g.close()
            except Exception:
                pass


_manager = GroupManager()


def _self_actor_hex() -> str:
    return getattr(get_runtime(), "_actor_hex", "")


# -- module-level API (reference collective.py signatures) ---------------

def _check_backend(backend: str) -> None:
    if backend not in ("host", "nccl", "gloo"):
        raise ValueError(f"unknown collective backend {backend!r}")


def init_collective_group(world_size: int, rank: int,
                          backend: str = "host",
                          group_name: str = "default") -> None:
    """Initialize this process's membership in a collective group.

    ``backend``: "host" (the p2p ring implemented here; "nccl"/"gloo"
    are accepted as aliases for reference compatibility — on TPU the
    accelerator tier lives inside jitted programs, see module
    docstring)."""
    _check_backend(backend)
    _manager.create(group_name, world_size, rank)


def create_collective_group(actors: Sequence, world_size: int,
                            ranks: Sequence[int],
                            backend: str = "host",
                            group_name: str = "default") -> None:
    """Declarative setup from the driver (reference collective.py:151):
    record the group membership; each actor joins lazily on first use."""
    _check_backend(backend)
    if len(actors) != len(ranks) or len(actors) != world_size:
        raise ValueError("actors/ranks must both have world_size entries")
    actor_ranks = {a._actor_hex: r for a, r in zip(actors, ranks)}
    internal_kv.kv_put(
        f"col-decl/{group_name}",
        {"world_size": world_size, "actor_ranks": actor_ranks})


def is_group_initialized(group_name: str = "default") -> bool:
    return _manager.get(group_name) is not None


def destroy_collective_group(group_name: str = "default") -> None:
    """Tear down this process's membership AND the cluster-wide state
    (declarative decl + any leftover rendezvous keys), so a destroyed
    group can't lazily resurrect or collide with a re-created one's
    restarted sequence numbers."""
    _manager.destroy(group_name)
    try:
        internal_kv.kv_del(f"col-decl/{group_name}")
        for k in internal_kv.kv_keys(f"colp2p/{group_name}/"):
            internal_kv.kv_del(k)
    except Exception:
        pass  # best effort: runtime may already be shut down


def get_rank(group_name: str = "default") -> int:
    g = _require(group_name)
    return g.rank


def get_collective_group_size(group_name: str = "default") -> int:
    g = _require(group_name)
    return g.world_size


def _require(group_name: str):
    g = _manager.get(group_name)
    if g is None:
        raise CollectiveGroupError(
            f"collective group {group_name!r} is not initialized in this "
            "process; call init_collective_group or "
            "create_collective_group first")
    return g


def allreduce(array, group_name: str = "default",
              op: ReduceOp = ReduceOp.SUM):
    return _require(group_name).allreduce(array, op)


def allgather(array, group_name: str = "default"):
    return _require(group_name).allgather(array)


def reducescatter(array, group_name: str = "default",
                  op: ReduceOp = ReduceOp.SUM):
    return _require(group_name).reducescatter(array, op)


def broadcast(array, src_rank: int = 0, group_name: str = "default"):
    return _require(group_name).broadcast(array, src_rank)


def send(array, dst_rank: int, group_name: str = "default"):
    return _require(group_name).send(array, dst_rank)


def recv(src_rank: int, group_name: str = "default"):
    return _require(group_name).recv(src_rank)


def barrier(group_name: str = "default"):
    _require(group_name).barrier()
