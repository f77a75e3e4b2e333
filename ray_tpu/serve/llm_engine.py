"""LLMEngine: continuous-batching inference over the paged KV cache.

Counterpart of the capability the reference gets from vLLM-over-ADAG
(SURVEY.md P12, §7.10) — owned here end to end, TPU-first:

  - one compiled prefill program per prompt-length bucket and ONE
    compiled decode program total ([max_batch] slots, static shapes);
  - page-granular cache memory via a free-list allocator, so long and
    short sequences share the pool with no fragmentation copies;
  - continuous batching: finished sequences release their slot + pages
    at the end of any step and queued requests join at the next one —
    the batch never drains to refill.

The engine is synchronous and single-host (one replica = one engine);
serve/llm.py wraps it as a deployment for scale-out across replicas.
"""

from __future__ import annotations

import math
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ray_tpu.models import transformer as tfm
from ray_tpu.models.decoding import decode_step, init_kv_pages, prefill
from ray_tpu.util import device_stats, flight_recorder, tracing
from ray_tpu.util.metrics import Counter, Gauge, Histogram

_REQUESTS = Counter(
    "ray_tpu_serve_requests_total",
    "Requests admitted into an LLMEngine queue.")
_SHED = Counter(
    "ray_tpu_serve_shed_total",
    "Requests shed by engine admission control.",
    tag_keys=("reason",))
_QUEUE_DEPTH = Gauge(
    "ray_tpu_serve_queue_depth",
    "Requests waiting in the engine admission queue.")
_KV_HANDOFF = Counter(
    "ray_tpu_serve_kv_handoff_total",
    "KV-page handoffs between prefill and decode replicas.",
    tag_keys=("direction",))
_KV_HANDOFF_BYTES = Counter(
    "ray_tpu_serve_kv_handoff_bytes_total",
    "KV page bytes moved by prefill->decode handoffs.",
    tag_keys=("direction",))
_HANDOFF_FALLBACK = Counter(
    "ray_tpu_serve_handoff_fallback_total",
    "Handoffs that fell back to re-prefill on the decode replica.",
    tag_keys=("reason",))
_QUEUE_WAIT = Histogram(
    "ray_tpu_serve_queue_wait_seconds",
    "Time a request spent in the engine admission queue, observed on "
    "EVERY outcome: admitted into a slot, or shed while waiting.",
    tag_keys=("outcome",))
_TTFT = Histogram(
    "ray_tpu_serve_ttft_seconds",
    "Time to first generated token (enqueue to first token).")
_TPOT = Histogram(
    "ray_tpu_serve_tpot_seconds",
    "Mean inter-token time after the first generated token.",
    boundaries=(0.0001, 0.001, 0.01, 0.1, 1.0, 10.0))


class QueueFull(RuntimeError):
    """Raised by add_request when the admission queue is at capacity.

    Backpressure signal: callers (LLMServer, proxies) translate it to
    HTTP 503 / retriable errors instead of letting the waiting queue —
    and every queued request's deadline — grow without bound."""


class RequestShed(RuntimeError):
    """Raised to a waiter whose queued request was shed (queueing
    deadline passed, or the request was aborted) before completing."""


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class PageAllocator:
    """Free-list page allocator (vLLM's block manager, minus CUDA)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        # The LAST physical page is the decode write path's scratch
        # target for inactive slots (ops/paged_attention.py
        # write_token_rows) — never allocate it.
        self._free: List[int] = list(range(num_pages - 2, -1, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"KV cache exhausted: need {n} pages, "
                f"{len(self._free)} free")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: Sequence[int]) -> None:
        self._free.extend(pages)


@dataclass
class _CacheEntry:
    page: int
    refcount: int
    depth: int  # chain position; leaves (deepest) evict first


class PrefixCache:
    """Hash-based sharing of full prompt-prefix KV pages across requests
    (the capability vLLM calls automatic prefix caching; the reference
    delegates it to vLLM — here it's in-tree and TPU-shaped: reuse only
    changes block tables and how much of the prompt the chunked-prefill
    program must process).

    A FULL page of `page_size` prompt tokens is keyed by the chain hash
    of every token up to and including that page, so a hit at page i
    implies hits at 0..i-1 and the block-table prefix can be reused
    verbatim. Pages enter with refcount 1 (the computing request);
    refcount-0 pages stay cached but evictable, deepest chains first (a
    child's reuse requires its parents, never vice versa)."""

    def __init__(self, page_size: int):
        self.page_size = page_size
        self._entries: Dict[bytes, _CacheEntry] = {}
        self.hits = 0
        self.tokens_saved = 0

    @staticmethod
    def chain_hashes(tokens: Sequence[int], page_size: int,
                     max_pages: int) -> List[bytes]:
        """Chain hash per full page: h_i = sha256(h_{i-1} || page
        tokens). Cryptographic, not Python hash(): a collision here
        would silently serve another prompt's KV pages."""
        import hashlib

        arr = np.asarray(tokens, dtype=np.int64)
        out: List[bytes] = []
        h = b""
        for i in range(max_pages):
            chunk = arr[i * page_size:(i + 1) * page_size].tobytes()
            h = hashlib.sha256(h + chunk).digest()
            out.append(h)
        return out

    def match(self, keys: Sequence[bytes]) -> List[int]:
        """Longest cached prefix: pages for keys[0..k), refcounts
        bumped. Stats are the ENGINE's to record on actual admission —
        a backpressured retry match+release must not inflate them."""
        pages = []
        for key in keys:
            e = self._entries.get(key)
            if e is None:
                break
            e.refcount += 1
            pages.append(e.page)
        return pages

    def peek(self, keys: Sequence[bytes]) -> int:
        """Length of the cached chain WITHOUT touching refcounts (the
        packed-admission eligibility probe)."""
        n = 0
        for key in keys:
            if key not in self._entries:
                break
            n += 1
        return n

    def register(self, key: bytes, page: int, depth: int) -> bool:
        """Adopt a freshly computed full prompt page (refcount 1, held
        by the computing request). False if the key is already cached
        (a concurrent identical prompt won the race): the caller keeps
        page ownership."""
        if key in self._entries:
            return False
        self._entries[key] = _CacheEntry(page, 1, depth)
        return True

    def release(self, keys: Sequence[bytes]) -> None:
        for key in keys:
            e = self._entries.get(key)
            if e is not None:
                e.refcount = max(0, e.refcount - 1)

    def evict(self, n: int) -> List[int]:
        """Free up to n unreferenced pages (deepest chains first)."""
        victims = sorted(
            (k for k, e in self._entries.items() if e.refcount == 0),
            key=lambda k: -self._entries[k].depth)[:n]
        return [self._entries.pop(k).page for k in victims]

    @property
    def num_idle(self) -> int:
        return sum(e.refcount == 0 for e in self._entries.values())

    def digest(self, k: int = 16) -> List[str]:
        """Top-k hot prefix keys (most-referenced first, shallower pages
        breaking ties) as truncated hex strings — the compact digest a
        replica's load_report carries so the router can prefix-match
        incoming prompts against what each replica already has cached."""
        keys = sorted(
            self._entries,
            key=lambda key: (-self._entries[key].refcount,
                             self._entries[key].depth))[:max(0, k)]
        return [key.hex()[:16] for key in keys]


@dataclass
class _Request:
    req_id: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    generated: List[int] = field(default_factory=list)
    slot: int = -1
    pages: List[int] = field(default_factory=list)  # privately owned
    eos_token: Optional[int] = None
    # Prefix-cache bookkeeping: chain keys this request holds refs on
    # (reused + self-registered); released on finish.
    cache_keys: List[bytes] = field(default_factory=list)
    # Full-prompt chain hashes, computed once (backpressure retries and
    # post-prefill registration reuse them).
    chain_keys: Optional[List[bytes]] = None
    # Speculative drafting: n-gram -> latest start index, maintained
    # incrementally so draft lookup is O(1) per decode step.
    ngram_index: Dict[tuple, int] = field(default_factory=dict)
    indexed_upto: int = 0
    # Queueing deadline (time.monotonic(); 0 = none): still WAITING past
    # it means the request is shed at the next step — admitted requests
    # always run to completion.
    deadline: float = 0.0
    enqueued_at: float = 0.0
    # Prefill->decode handoff: a serve_kv_export bundle whose pages this
    # request splices into the local cache at admission instead of
    # re-running prefill (import_kv / _admit_import).
    kv_bundle: Optional[Dict[str, Any]] = None
    # Prefill-specialized replicas set this: when the request finishes,
    # its KV pages are exported into kv_ready BEFORE the pages are
    # freed, so the bundle capture cannot race the engine thread.
    export_on_finish: bool = False
    # Request-journey trace context (trace_id, parent_span_id) threaded
    # from the ingress proxy via the replica call; phase spans
    # (serve.queue/prefill/decode) parent under it.  None = untraced.
    trace_ctx: Optional[tuple] = None
    # Phase timeline, epoch seconds (0.0 = not reached): enqueue into
    # the waiting queue, seated into a slot, first generated token.
    # The derived SLO sample (TTFT/TPOT/queue-wait) folds into
    # slo_samples at finish.
    t_enqueue: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    # Whether the request resumed from an imported KV bundle (its
    # admission phase is a page splice, not a prefill).
    imported: bool = False


class LLMEngine:
    def __init__(self, config: tfm.TransformerConfig,
                 params: Optional[Dict[str, Any]] = None, *,
                 page_size: int = 16, num_pages: int = 512,
                 max_batch: int = 8, seed: int = 0,
                 enable_prefix_caching: bool = True,
                 speculative_k: int = 0, speculative_ngram: int = 2,
                 multi_step: int = 1, pipeline_depth: int = 2,
                 packed_admit: bool = True,
                 prefill_wave_tokens: int = 8192,
                 prefill_row_tokens: int = 1024,
                 max_queue: Optional[int] = None,
                 queue_timeout_s: Optional[float] = None,
                 prefill_budget: Optional[int] = None):
        import jax

        c = config
        self.config = c
        self.page_size = page_size
        self.max_batch = max_batch
        # Speculative decoding (greedy prompt-lookup): draft up to k
        # tokens by matching the trailing n-gram earlier in the
        # sequence, verify them in ONE chunked forward. 0 disables.
        self.spec_k = int(speculative_k)
        self.spec_ngram = max(1, int(speculative_ngram))
        self.spec_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        # Multi-step decoding (greedy only): run n decode iterations on
        # device per engine step, syncing tokens to the host once — the
        # host-overhead/dispatch-latency amortizer (models/decoding.py
        # decode_multi_step). 1 = classic per-token stepping.
        self.multi_step = max(1, int(multi_step))
        # Pipelined chunk dispatch (greedy multi-step only): chunk k+1
        # is dispatched off chunk k's DEVICE-resident final state
        # (decode_multi_step returns tokens/positions/ctx as device
        # arrays) while chunk k's token transfer is still in flight, so
        # the device runs back-to-back and the host round-trip latency
        # hides behind compute instead of stalling every chunk.
        # Admissions fold in
        # between chunks via merge_slot_state — continuous batching
        # keeps its <= multi_step-token admission latency WITHOUT
        # paying a sync per chunk.  Depth 1 = dispatch-then-reconcile
        # (classic synchronous behavior).
        self.pipeline_depth = max(1, int(pipeline_depth))
        # Packed async admission (greedy pipelined path): waiting
        # prompts are padded to a pow-2 page-multiple bucket, packed
        # into long rows (matmul-efficient layout), prefilled AND
        # folded into the device decode state in one dispatch — the
        # first tokens come back off the critical path, so admission
        # never stalls in-flight decode chunks on a host sync
        # (models/decoding.py packed_prefill_admit).
        self.packed_admit = bool(packed_admit) \
            and page_size & (page_size - 1) == 0
        self.prefill_wave_tokens = max(page_size,
                                       int(prefill_wave_tokens))
        self.prefill_row_tokens = max(page_size, int(prefill_row_tokens))
        # Step-classification counters (benchmarks use these to tell
        # pure-decode steps from ones that did admission work).
        self.waves_dispatched = 0
        self.prefill_reconciles = 0
        self._inflight: List[dict] = []  # FIFO of dispatched chunks
        self._dstate = None  # device (tokens, positions, ctx, lim, eos)
        self._dirty_slots: set = set()  # freed slots to zero on device
        self._just_admitted: set = set()  # slots to fold into dstate
        self.max_pages_per_seq = math.ceil(c.max_seq_len / page_size)
        params = params if params is not None else tfm.init_params(
            c, jax.random.key(seed))
        # Serve in the compute dtype: params arrive in param_dtype (fp32
        # master weights — a training artifact), but every decode
        # iteration streams ALL weights from HBM, so fp32 storage would
        # double the traffic of the bandwidth-bound decode step and cap
        # the engine at half its roofline.  The forward casts per-use
        # (`.astype(c.dtype)`), so a one-time cast here is numerically
        # identical and makes the per-step reads bf16-sized.
        import jax.numpy as jnp

        self.params = jax.tree.map(
            lambda x: x.astype(c.dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x, params)
        self.cache = init_kv_pages(c, num_pages, page_size)
        self.allocator = PageAllocator(num_pages)
        self.prefix_cache = (PrefixCache(page_size)
                             if enable_prefix_caching else None)
        self._rng = np.random.default_rng(seed)

        # Slot state (fixed [max_batch] shapes → one compiled decode).
        self.block_tables = np.zeros(
            (max_batch, self.max_pages_per_seq), dtype=np.int32)
        self.context_lens = np.zeros(max_batch, dtype=np.int32)
        self.last_tokens = np.zeros(max_batch, dtype=np.int32)
        self.slot_req: List[Optional[_Request]] = [None] * max_batch

        self._next_id = 0
        self.waiting: List[_Request] = []
        self.num_completed = 0
        # Prefill/decode disaggregation counters (serve observability).
        self.kv_exports = 0
        self.kv_imports = 0
        # Completions surfaced by an out-of-band pipeline flush (e.g.
        # export_kv draining in-flight chunks); merged into the next
        # step()'s done map so no finish is ever dropped.
        self._pending_done: Dict[int, List[int]] = {}
        # req_id -> serve_kv_export bundle captured at finish for
        # export_on_finish requests (bounded; oldest evicted first).
        self.kv_ready: Dict[int, Dict[str, Any]] = {}

        # Admission control (serve data plane): a bounded waiting queue
        # (add_request raises QueueFull past it), a queueing deadline
        # past which still-waiting requests are shed at the next step,
        # and a per-step prefill token budget so admission work can't
        # starve in-flight decode slots (TPOT stays flat while prompts
        # prefill).  0 disables each mechanism.
        self.max_queue = (_env_int("RAY_TPU_SERVE_MAX_QUEUE", 1024)
                          if max_queue is None else int(max_queue))
        self.queue_timeout_s = (
            _env_float("RAY_TPU_SERVE_QUEUE_TIMEOUT_S", 60.0)
            if queue_timeout_s is None else float(queue_timeout_s))
        self.prefill_budget = (
            _env_int("RAY_TPU_SERVE_PREFILL_BUDGET", 8192)
            if prefill_budget is None else int(prefill_budget))
        self.num_shed = 0
        self.num_aborted = 0
        # Requests shed/aborted since the caller last drained this map
        # ({req_id: reason}); serve/llm.py fails the matching waiters.
        self.shed: Dict[int, str] = {}
        self._step_prefill_left = 1 << 30
        # Per-request SLO samples (TTFT/TPOT/queue-wait), appended at
        # finish (queue-wait-only at shed) and drained by stats() ->
        # load_report -> controller sliding windows (/api/serve_slo).
        from collections import deque

        self.slo_samples: deque = deque(maxlen=max(
            1, _env_int("RAY_TPU_SERVE_SLO_SAMPLES", 256)))
        # Low-overhead per-step sampler: every Nth step snapshots batch
        # occupancy, queue depth, free KV pages and the previous step's
        # prefill-token spend into engine_sample (0 disables).  One
        # small dict assignment — no device sync, no allocation scan.
        self._sample_every = _env_int(
            "RAY_TPU_SERVE_STEP_SAMPLE_EVERY", 8)
        self._step_count = 0
        self.engine_sample: Optional[Dict[str, Any]] = None
        # Device-plane attribution: modeled per-token traffic/compute
        # terms (the same ones bench_decode uses offline) so the step
        # sampler can emit continuous roofline/MFU, plus HBM ledger
        # entries for the two big resident pools.
        self._weight_bytes = int(sum(
            x.size * x.dtype.itemsize
            for x in jax.tree.leaves(self.params)
            if hasattr(x, "dtype")))
        self._kv_per_token_bytes = int(
            2 * c.num_layers * c.num_kv_heads * c.head_dim_
            * jnp.dtype(c.dtype).itemsize)
        self._flops_per_token = 2 * tfm.num_params(c)
        device_stats.attribute("weights", self._weight_bytes)
        device_stats.attribute("kv_pages", int(sum(
            v.size * v.dtype.itemsize for v in self.cache.values())))
        self._finished_tokens = 0
        self._last_sample_t: Optional[float] = None
        self._last_sample_tokens = 0

    # -- public API --------------------------------------------------------
    def add_request(self, prompt_tokens: Sequence[int],
                    max_new_tokens: int = 32, *,
                    temperature: float = 0.0,
                    eos_token: Optional[int] = None,
                    deadline_s: Optional[float] = None,
                    export_on_finish: bool = False,
                    trace_ctx: Optional[tuple] = None) -> int:
        if not prompt_tokens:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if (len(prompt_tokens) + max_new_tokens) > self.config.max_seq_len:
            raise ValueError(
                f"prompt+generation ({len(prompt_tokens)}+{max_new_tokens})"
                f" exceeds max_seq_len={self.config.max_seq_len}")
        need = math.ceil(
            (len(prompt_tokens) + max_new_tokens) / self.page_size)
        # num_pages - 1: the last physical page is the decode scratch
        # target (PageAllocator) and can never be allocated.
        if need > self.allocator.num_pages - 1:
            # Would never be admittable — it would wedge the FIFO queue.
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.allocator.num_pages - 1} allocatable; raise "
                "num_pages or shorten the request")
        if self.max_queue > 0 and len(self.waiting) >= self.max_queue:
            # Backpressure instead of unbounded queue growth: shedding
            # at the door is the one point where the caller can still
            # retry another replica.
            self.num_shed += 1
            _SHED.inc(tags={"reason": "queue_full"})
            flight_recorder.record("serve", "queue_full",
                                   waiting=len(self.waiting),
                                   max_queue=self.max_queue)
            raise QueueFull(
                f"admission queue full ({len(self.waiting)} waiting, "
                f"cap {self.max_queue})")
        req = _Request(self._next_id, list(prompt_tokens), max_new_tokens,
                       temperature, eos_token=eos_token,
                       export_on_finish=export_on_finish)
        if trace_ctx:
            req.trace_ctx = tuple(trace_ctx)
        req.t_enqueue = time.time()
        req.enqueued_at = time.monotonic()
        ttl = self.queue_timeout_s if deadline_s is None else deadline_s
        if ttl and ttl > 0:
            req.deadline = req.enqueued_at + ttl
        self._next_id += 1
        self.waiting.append(req)
        _REQUESTS.inc()
        _QUEUE_DEPTH.set(len(self.waiting))
        return req.req_id

    def abort(self, req_id: int, reason: str = "aborted") -> bool:
        """Cancel a request wherever it is (waiting or active) and
        reclaim its slot + KV pages.  Mid-stream client disconnects land
        here: the slot frees at the next device-state merge, so an
        abandoned generation stops burning decode bandwidth.  Returns
        False when the id is unknown (already finished or shed)."""
        for i, req in enumerate(self.waiting):
            if req.req_id == req_id:
                self.waiting.pop(i)
                self._retire_unstarted(req, reason)
                _QUEUE_DEPTH.set(len(self.waiting))
                return True
        for slot, req in enumerate(self.slot_req):
            if req is not None and req.req_id == req_id:
                # Mirror _maybe_finish's retirement, minus completion
                # accounting: free the slot + private pages, release
                # prefix-cache refs, and mark the slot dirty so the
                # next merge zeroes it device-side (in-flight chunks
                # then skip it at reconcile: slot_req identity check).
                self.slot_req[slot] = None
                self.context_lens[slot] = 0
                self.allocator.free(req.pages)
                req.pages = []
                if self.prefix_cache is not None and req.cache_keys:
                    self.prefix_cache.release(req.cache_keys)
                    req.cache_keys = []
                self._dirty_slots.add(slot)
                self.num_aborted += 1
                self.shed[req_id] = reason
                flight_recorder.record("serve", "abort", req_id=req_id,
                                       reason=reason, slot=slot)
                return True
        return False

    def export_kv(self, req_id: int) -> Dict[str, Any]:
        """Export an ACTIVE request's KV pages + resume state as a
        `serve_kv_export` wire message — the prefill side of the
        prefill->decode handoff.  The bundle carries everything a decode
        engine needs to resume generation without re-running prefill:
        the prompt, tokens generated so far, the context length, the
        prefix-cache chain keys, and the [L, n_ctx, page, KD] K/V page
        tensors read out of the paged cache in one gather
        (models/decoding.py gather_kv_pages).  The request stays active
        here; the caller aborts it once the bundle is shipped."""
        import jax.numpy as jnp

        from ray_tpu.models.decoding import gather_kv_pages

        slot, req = -1, None
        for s, r in enumerate(self.slot_req):
            if r is not None and r.req_id == req_id:
                slot, req = s, r
                break
        if req is None:
            raise KeyError(f"request {req_id} is not active")
        if self._inflight:
            # Host mirrors (context_lens, generated) must be
            # authoritative before reading them: drain the pipeline.
            # Completions it surfaces merge into the next step()'s done
            # map, so no finish is dropped.
            self._flush_pipeline(self._pending_done)
            if self.slot_req[slot] is not req:
                raise KeyError(f"request {req_id} finished before export")
        if not req.generated:
            raise RuntimeError(
                f"request {req_id} has no generated token yet")
        return self._kv_bundle(req, slot, int(self.context_lens[slot]))

    def _kv_bundle(self, req: _Request, slot: int,
                   ctx: int) -> Dict[str, Any]:
        """Gather slot's first ceil(ctx/page_size) KV pages into a
        serve_kv_export bundle.  Caller guarantees the device cache
        holds KV for positions [0, ctx) of this slot."""
        import jax.numpy as jnp

        from ray_tpu.models.decoding import gather_kv_pages

        n_ctx = max(1, math.ceil(ctx / self.page_size))
        # Pow-2 pad the gather (compile reuse); pad rows read an
        # arbitrary live page and are sliced off host-side.
        N = 1 << (n_ctx - 1).bit_length()
        ids = np.zeros(N, dtype=np.int32)
        ids[:n_ctx] = self.block_tables[slot][:n_ctx]
        k, v = gather_kv_pages(self.cache, jnp.asarray(ids))
        k = np.asarray(k)[:, :n_ctx]
        v = np.asarray(v)[:, :n_ctx]
        bundle: Dict[str, Any] = {
            "op": "serve_kv_export",
            "req": req.req_id,
            "prompt": list(req.prompt),
            "generated": list(req.generated),
            "context_len": ctx,
            "page_size": self.page_size,
            "num_layers": int(k.shape[0]),
            "kd": int(k.shape[-1]),
            "dtype": str(k.dtype),
            "chain_keys": list(req.chain_keys or []),
            "k": k,
            "v": v,
        }
        self.kv_exports += 1
        nbytes = k.nbytes + v.nbytes
        _KV_HANDOFF.inc(tags={"direction": "export"})
        _KV_HANDOFF_BYTES.inc(nbytes, tags={"direction": "export"})
        flight_recorder.record("serve", "kv_export", req_id=req.req_id,
                               pages=n_ctx, bytes=nbytes)
        return bundle

    def import_kv(self, bundle: Dict[str, Any],
                  max_new_tokens: int = 32, *,
                  temperature: float = 0.0,
                  eos_token: Optional[int] = None,
                  deadline_s: Optional[float] = None,
                  trace_ctx: Optional[tuple] = None) -> int:
        """Enqueue a request resuming from an exported KV bundle — the
        decode side of the prefill->decode handoff.  Mirrors
        add_request's admission contract (bounds checks, QueueFull
        backpressure, deadlines); the actual page splice happens at
        admission time (_admit_import), where slot + pages exist.
        max_new_tokens is the request's TOTAL decode budget, counting
        tokens the prefill replica already generated."""
        from ray_tpu.core import wire_schema

        wire_schema.validate(bundle)
        if bundle.get("op") != "serve_kv_export":
            raise ValueError(
                f"expected serve_kv_export bundle, got {bundle.get('op')}")
        for key, want in (("page_size", self.page_size),
                          ("num_layers", self.config.num_layers)):
            if int(bundle[key]) != want:
                raise ValueError(
                    f"KV bundle {key}={bundle[key]} incompatible with "
                    f"engine {key}={want}")
        if str(np.asarray(bundle["k"]).dtype) != \
                str(np.asarray(self.cache["k"]).dtype):
            raise ValueError(
                f"KV bundle dtype {bundle['dtype']} incompatible with "
                f"cache dtype {np.asarray(self.cache['k']).dtype}")
        prompt = list(bundle["prompt"])
        generated = list(bundle["generated"])
        if not prompt:
            raise ValueError("bundle prompt must contain at least one token")
        if not generated:
            raise ValueError("bundle carries no generated token to resume")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if len(generated) >= max_new_tokens:
            raise ValueError(
                f"bundle already has {len(generated)} generated tokens; "
                f"nothing left of a {max_new_tokens}-token budget")
        if (len(prompt) + max_new_tokens) > self.config.max_seq_len:
            raise ValueError(
                f"prompt+generation ({len(prompt)}+{max_new_tokens})"
                f" exceeds max_seq_len={self.config.max_seq_len}")
        need = math.ceil((len(prompt) + max_new_tokens) / self.page_size)
        if need > self.allocator.num_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.allocator.num_pages - 1} allocatable; raise "
                "num_pages or shorten the request")
        if int(bundle["context_len"]) != \
                len(prompt) + len(generated) - 1:
            raise ValueError(
                f"bundle context_len {bundle['context_len']} does not "
                f"match prompt+generated-1 "
                f"({len(prompt)}+{len(generated)}-1)")
        if self.max_queue > 0 and len(self.waiting) >= self.max_queue:
            self.num_shed += 1
            _SHED.inc(tags={"reason": "queue_full"})
            flight_recorder.record("serve", "queue_full",
                                   waiting=len(self.waiting),
                                   max_queue=self.max_queue)
            raise QueueFull(
                f"admission queue full ({len(self.waiting)} waiting, "
                f"cap {self.max_queue})")
        req = _Request(self._next_id, prompt, max_new_tokens,
                       temperature, generated=generated,
                       eos_token=eos_token)
        req.kv_bundle = bundle
        req.imported = True
        if trace_ctx:
            req.trace_ctx = tuple(trace_ctx)
        keys = bundle.get("chain_keys")
        if keys:
            req.chain_keys = [bytes(k) for k in keys]
        req.t_enqueue = time.time()
        req.enqueued_at = time.monotonic()
        ttl = self.queue_timeout_s if deadline_s is None else deadline_s
        if ttl and ttl > 0:
            req.deadline = req.enqueued_at + ttl
        self._next_id += 1
        self.waiting.append(req)
        _REQUESTS.inc()
        _QUEUE_DEPTH.set(len(self.waiting))
        return req.req_id

    def _retire_unstarted(self, req: _Request, reason: str) -> None:
        """Drop a request that never reached a slot (shed or aborted
        while waiting).  Waiting requests hold no pages and no
        prefix-cache refs (_admit releases them on backpressure), so
        this is pure queue bookkeeping."""
        self.num_shed += 1
        self.shed[req.req_id] = reason
        _SHED.inc(tags={"reason": reason})
        now = time.time()
        waited = (time.monotonic() - req.enqueued_at
                  if req.enqueued_at else 0.0)
        # Queue wait is observed on EVERY outcome — sheds included —
        # so the histogram reflects what waiting requests experienced,
        # not just the survivors.
        _QUEUE_WAIT.observe(max(0.0, waited), tags={"outcome": "shed"})
        self.slo_samples.append({
            "queue_wait": round(max(0.0, waited), 6),
            "shed": reason, "ts": now})
        if req.trace_ctx is not None:
            # Partial timeline: a shed request still leaves its queue
            # phase in the trace (end attribute says why it ended).
            tracing.record_span(
                "serve.queue", req.t_enqueue or now - waited, now,
                attributes={"req": req.req_id, "shed": reason,
                            "clock_off": round(tracing.clock_offset(),
                                               6)},
                parent_id=req.trace_ctx[1] or None,
                trace_id=req.trace_ctx[0], force=True)
        flight_recorder.record(
            "serve", "shed", req_id=req.req_id, reason=reason,
            waited_s=round(waited, 3) if req.enqueued_at else 0.0)

    def _note_admitted(self, req: _Request) -> None:
        """Seat-time bookkeeping shared by every admission path
        (classic _admit, KV import, packed wave): the queue-wait
        histogram plus the serve.queue phase span of traced requests."""
        now = time.time()
        req.t_admit = now
        waited = (time.monotonic() - req.enqueued_at
                  if req.enqueued_at else 0.0)
        _QUEUE_WAIT.observe(max(0.0, waited),
                            tags={"outcome": "admitted"})
        if req.trace_ctx is not None:
            tracing.record_span(
                "serve.queue", req.t_enqueue or now - waited, now,
                attributes={"req": req.req_id,
                            "clock_off": round(tracing.clock_offset(),
                                               6)},
                parent_id=req.trace_ctx[1] or None,
                trace_id=req.trace_ctx[0], force=True)

    def _stamp_first(self, req: _Request) -> None:
        """First generated token (or KV splice done): closes the
        prefill/import phase.  Idempotent — every path that appends a
        first token calls it."""
        if req.t_first:
            return
        req.t_first = time.time()
        if req.trace_ctx is not None and req.t_admit:
            tracing.record_span(
                "serve.import" if req.imported else "serve.prefill",
                req.t_admit, req.t_first,
                attributes={"req": req.req_id,
                            "prompt_tokens": len(req.prompt)},
                parent_id=req.trace_ctx[1] or None,
                trace_id=req.trace_ctx[0], force=True)

    def _note_finished(self, req: _Request) -> None:
        """Finish-time SLO accounting: TTFT/TPOT histograms, the SLO
        sample ring (controller sliding windows fold it), and the
        decode phase span of traced requests."""
        now = time.time()
        if not req.t_first:
            req.t_first = now
        ttft = (max(0.0, req.t_first - req.t_enqueue)
                if req.t_enqueue else 0.0)
        n_out = len(req.generated)
        tpot = (max(0.0, now - req.t_first) / (n_out - 1)
                if n_out > 1 else 0.0)
        qwait = (max(0.0, (req.t_admit or req.t_first) - req.t_enqueue)
                 if req.t_enqueue else 0.0)
        _TTFT.observe(ttft)
        _TPOT.observe(tpot)
        self._finished_tokens += n_out
        self.slo_samples.append({
            "ttft": round(ttft, 6), "tpot": round(tpot, 6),
            "queue_wait": round(qwait, 6), "tokens": n_out, "ts": now})
        if req.trace_ctx is not None:
            tracing.record_span(
                "serve.decode", req.t_first, now,
                attributes={"req": req.req_id, "tokens": n_out,
                            "tpot": round(tpot, 6)},
                parent_id=req.trace_ctx[1] or None,
                trace_id=req.trace_ctx[0], force=True)

    def _shed_expired(self) -> None:
        """Deadline-based shedding: drop waiting requests whose
        queueing deadline passed.  Runs at the top of every step —
        between steps nothing could have admitted them anyway."""
        if not self.waiting:
            return
        now = time.monotonic()
        kept: List[_Request] = []
        for req in self.waiting:
            if req.deadline and now > req.deadline:
                self._retire_unstarted(req, "deadline")
            else:
                kept.append(req)
        if len(kept) != len(self.waiting):
            self.waiting = kept
        _QUEUE_DEPTH.set(len(self.waiting))

    def _sample_device(self, sample: Dict[str, Any]) -> None:
        """Device-plane extension of the every-Nth-step sampler: fold
        modeled bytes+flops over the tokens emitted since the last
        sampled step into continuous roofline/MFU gauges, a periodic
        `device.step` span, and the engine_sample itself (which rides
        load_report to the controller unchanged).  Host math on values
        the engine already tracks — no device sync."""
        now = sample["ts"]
        total = self._finished_tokens + sum(
            len(r.generated) for r in self.slot_req if r is not None)
        prev_t, prev_tok = self._last_sample_t, self._last_sample_tokens
        self._last_sample_t, self._last_sample_tokens = now, total
        if not device_stats.enabled() or prev_t is None \
                or now <= prev_t:
            return
        try:
            tok_s = max(0, total - prev_tok) / (now - prev_t)
            # Every decode iteration streams the full weights plus the
            # live KV context; amortize per token over the batch.
            active = max(1, self.num_active)
            live_ctx = int(self.context_lens.sum())
            bytes_per_token = (
                self._weight_bytes
                + live_ctx * self._kv_per_token_bytes) / active
            frac, mfu = device_stats.note_step(
                tokens_per_s=tok_s, bytes_per_token=bytes_per_token,
                flops_per_token=self._flops_per_token, plane="serve",
                extra={"active": sample["active"],
                       "step": sample["step"]})
            sample["tokens_per_s"] = round(tok_s, 2)
            sample["modeled_bytes_per_token"] = int(bytes_per_token)
            attrs = {"plane": "serve", "tokens_per_s": round(tok_s, 2),
                     "active": sample["active"]}
            if frac is not None:  # None: this device's peaks are unknown
                attrs["roofline_fraction"] = round(frac, 5)
                attrs["mfu"] = round(mfu, 5)
                sample["roofline_fraction"] = attrs["roofline_fraction"]
                sample["mfu"] = attrs["mfu"]
            tracing.record_span("device.step", prev_t, now,
                                attributes=attrs)
        except Exception:  # raylint: allow-swallow(telemetry must never fail an engine step)
            pass

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    def has_work(self) -> bool:
        return bool(self.waiting) or self.num_active > 0 \
            or bool(self._inflight) or bool(self._pending_done)

    def step(self) -> Dict[int, List[int]]:
        """Admit waiting requests (prefill), then one batched decode step
        (a pipelined multi_step chunk on the greedy path).  Returns
        requests that finished THIS step ({req_id: tokens}); with
        pipelining, a request's completion surfaces when its chunk's
        tokens are reconciled (<= pipeline_depth steps after the chunk
        that produced them)."""
        done: Dict[int, List[int]] = {}
        if self._pending_done:
            done.update(self._pending_done)
            self._pending_done.clear()
        self._step_count += 1
        if self._sample_every > 0 \
                and self._step_count % self._sample_every == 0:
            # Snapshot BEFORE this step's work: _step_prefill_left still
            # holds the previous step's remainder, so prefill_tokens is
            # that step's actual prompt-token spend.
            budget = (self.prefill_budget
                      if self.prefill_budget > 0 else 0)
            self.engine_sample = {
                "ts": time.time(),
                "step": self._step_count,
                "active": self.num_active,
                "waiting": len(self.waiting),
                "free_pages": self.allocator.num_free,
                "inflight_chunks": len(self._inflight),
                "prefill_tokens": (
                    max(0, budget - min(self._step_prefill_left,
                                        budget)) if budget else 0),
                "completed": self.num_completed,
            }
            self._sample_device(self.engine_sample)
        self._shed_expired()
        # Per-step prefill token budget: admission (classic _admit and
        # packed waves) may spend at most this many prompt tokens per
        # step, so a prefill burst interleaves with decode in bounded
        # chunks instead of stalling every live slot for a full wave.
        self._step_prefill_left = (self.prefill_budget
                                   if self.prefill_budget > 0
                                   else (1 << 30))
        if self._pipelined_ok():
            # Completed in-flight work costs nothing to fold in.
            self._eager_reconcile(done)
            # Admissions need free slots: recycle the oldest in-flight
            # chunk first when the queue would otherwise starve.
            if self.waiting and not self._free_slots() and self._inflight:
                self._reconcile_oldest(done)
            self._dispatch_prefill_wave()
            if self.waiting and self._free_slots() \
                    and not self._wave_eligible(self.waiting[0]):
                # Head of queue needs the classic synchronous path
                # (sampling, prefix-cache hit, packed admission off).
                done.update(self._admit())
                if not self._pipelined_ok():
                    # An admission just seated a sampling request: drain
                    # and run this step on the classic per-token path.
                    self._flush_pipeline(done)
                    if self.num_active:
                        done.update(self._decode())
                    return done
            dispatched = self._dispatch_chunk()
            ndecode = sum(1 for ch in self._inflight
                          if ch.get("type") != "prefill")
            if ndecode >= self.pipeline_depth \
                    or (self._inflight and not dispatched):
                self._reconcile_oldest(done)
            return done
        self._flush_pipeline(done)
        done.update(self._admit())
        if self.num_active:
            done.update(self._decode())
        return done

    def _pipelined_ok(self) -> bool:
        """Pipelined chunk decode serves the greedy multi-step path;
        sampling and speculative slots need per-token host control and
        fall back to the classic synchronous step.  Only ACTIVE slots
        are checked: a sampling request still in the queue must not
        degrade a full greedy batch (it can't run anyway until a slot
        frees); the post-admission re-check in step() handles the
        moment it actually lands."""
        if self.multi_step <= 1 or self.spec_k > 0:
            return False
        return not any(r is not None and r.temperature > 0.0
                       for r in self.slot_req)

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32, *,
                 temperature: float = 0.0) -> List[List[int]]:
        """Blocking batch generation (greedy by default)."""
        ids = [self.add_request(p, max_new_tokens, temperature=temperature)
               for p in prompts]
        results: Dict[int, List[int]] = {}
        while self.has_work():
            results.update(self.step())
        return [results[i] for i in ids]

    # -- internals ---------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _alloc_evicting(self, n: int) -> List[int]:
        """Allocate n pages, reclaiming idle prefix-cache pages when the
        free list runs short (vLLM's evictor path)."""
        short = n - self.allocator.num_free
        if short > 0 and self.prefix_cache is not None:
            self.allocator.free(self.prefix_cache.evict(short))
        return self.allocator.alloc(n)

    def _available_pages(self) -> int:
        idle = (self.prefix_cache.num_idle
                if self.prefix_cache is not None else 0)
        return self.allocator.num_free + idle

    def _admit(self) -> Dict[int, List[int]]:
        import jax.numpy as jnp

        from ray_tpu.models.decoding import prefill_with_context

        done: Dict[int, List[int]] = {}
        free = self._free_slots()
        # Phase 1: admit (slot + page allocation, table build) WITHOUT
        # prefilling, so phase 2 can batch uncached prompts of one
        # length bucket into a single prefill program — one device sync
        # for the whole admission wave instead of one per request.
        admitted: List[tuple] = []  # (req, shared, pages, start)
        # Prompt-page keys the CURRENT wave will register: a same-wave
        # request sharing a prefix is deferred one step so it admits
        # against the registered cache instead of recomputing (keeps the
        # sequential path's dedup for shared-prefix bursts).
        pending_keys: set = set()
        while self.waiting and free:
            req = self.waiting[0]
            if req.kv_bundle is not None:
                # Imported KV needs no prefill (budget-exempt): splice
                # its pages in and arm the decode slot directly.
                if not self._admit_import(req, free, done):
                    break
                continue
            L = len(req.prompt)
            total = math.ceil((L + req.max_new_tokens) / self.page_size)

            # Prefix-cache hit: reuse the longest chain of FULL prompt
            # pages, capped so at least one prompt token is recomputed
            # (its logits seed sampling of the first generated token).
            shared: List[int] = []
            if self.prefix_cache is not None:
                if req.chain_keys is None:
                    req.chain_keys = PrefixCache.chain_hashes(
                        req.prompt, self.page_size, L // self.page_size)
                if req.chain_keys and req.chain_keys[0] in pending_keys:
                    break  # defer: this wave is computing its prefix
                matchable = max(0, (L - 1) // self.page_size)
                shared = self.prefix_cache.match(
                    req.chain_keys[:matchable])
                req.cache_keys = req.chain_keys[:len(shared)]
            n_private = total - len(shared)
            if n_private > self._available_pages():
                # Backpressure: release the reservation and wait.
                if self.prefix_cache is not None and req.cache_keys:
                    self.prefix_cache.release(req.cache_keys)
                    req.cache_keys = []
                break
            n_suffix = L - len(shared) * self.page_size
            if (admitted or self.num_active or self._inflight) \
                    and n_suffix > self._step_prefill_left:
                # Step prefill budget spent: defer so live decode slots
                # get their step; an idle engine admits regardless.
                if self.prefix_cache is not None and req.cache_keys:
                    self.prefix_cache.release(req.cache_keys)
                    req.cache_keys = []
                break
            self._step_prefill_left = max(
                0, self._step_prefill_left - n_suffix)
            self.waiting.pop(0)
            self._note_admitted(req)
            slot = free.pop(0)
            req.slot = slot
            req.pages = self._alloc_evicting(n_private)
            pages = shared + req.pages
            table = np.zeros(self.max_pages_per_seq, dtype=np.int32)
            table[:len(pages)] = pages
            self.block_tables[slot] = table
            if self.prefix_cache is not None and req.chain_keys:
                pending_keys.update(
                    req.chain_keys[:L // self.page_size])
            admitted.append((req, shared, pages,
                             len(shared) * self.page_size))

        # Phase 2: prefill.  Uncached prompts (start == 0) batch by
        # pow-2 suffix bucket; cache-hit suffixes keep the per-request
        # chunked path (their table widths differ).
        groups: Dict[int, List[tuple]] = {}
        singles: List[tuple] = []
        for item in admitted:
            req, shared, pages, start = item
            n_suffix = len(req.prompt) - start
            S = max(8, 1 << (n_suffix - 1).bit_length())
            if start == 0:
                groups.setdefault(S, []).append(item)
            else:
                singles.append((item, S))

        for S, items in groups.items():
            # Batch dim bucketed pow-2 (pad rows carry positions=-1, so
            # their K/V writes drop) — one compile per (B, S) bucket.
            B = 1 << (len(items) - 1).bit_length()
            tokens = np.zeros((B, S), dtype=np.int32)
            positions = np.full((B, S), -1, dtype=np.int32)
            tables = np.zeros((B, self.max_pages_per_seq),
                              dtype=np.int32)
            for r, (req, _, _, _) in enumerate(items):
                L = len(req.prompt)
                tokens[r, :L] = req.prompt
                positions[r, :L] = np.arange(L)
                tables[r] = self.block_tables[req.slot]
            logits, self.cache = prefill(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                self.cache, jnp.asarray(tables), self.config)
            logits = np.asarray(logits)  # one sync for the whole group
            for r, item in enumerate(items):
                self._finish_admit(item, logits[r], done)

        for (item, S) in singles:
            req, shared, pages, start = item
            L = len(req.prompt)
            n_suffix = L - start
            tokens = np.zeros((1, S), dtype=np.int32)
            tokens[0, :n_suffix] = req.prompt[start:]
            positions = np.full((1, S), -1, dtype=np.int32)
            positions[0, :n_suffix] = np.arange(start, L)
            # Chunked prefill gathers the WHOLE table width as attention
            # context; bucket it to the pages this prompt actually spans
            # (pow-2 for compile reuse) so a short cached prompt doesn't
            # pay max_seq_len-wide attention.
            W = min(self.max_pages_per_seq, max(1, 1 << (
                math.ceil(L / self.page_size) - 1).bit_length()))
            table = self.block_tables[req.slot]
            logits, self.cache = prefill_with_context(
                self.params, jnp.asarray(tokens),
                jnp.asarray(positions), self.cache,
                jnp.asarray(table[:W][None]), self.config)
            self._finish_admit(item, np.asarray(logits)[0], done)
        return done

    def _finish_admit(self, item: tuple, logits_row: np.ndarray,
                      done: Dict[int, List[int]]):
        """Post-prefill bookkeeping for one admitted request: adopt its
        full prompt pages into the prefix cache, sample the first token,
        arm the decode slot."""
        req, shared, pages, start = item
        L = len(req.prompt)
        # Adopt ALL full prompt pages this request just computed into
        # the cache (depth = page index; leaves evict first). A full
        # prompt page never receives later writes — generation
        # continues in the partial/next page — so it is immutable.
        if self.prefix_cache is not None:
            if shared:
                self.prefix_cache.hits += 1
                self.prefix_cache.tokens_saved += start
            full = L // self.page_size
            own = []
            for i in range(len(shared), full):
                page = pages[i]
                if self.prefix_cache.register(req.chain_keys[i], page, i):
                    req.cache_keys.append(req.chain_keys[i])
                    own.append(page)
            # Registered pages now belong to the cache, not the
            # request's private set.
            req.pages = [p for p in req.pages if p not in own]

        next_tok = self._sample(logits_row, req)
        self.context_lens[req.slot] = L
        self.last_tokens[req.slot] = next_tok
        req.generated.append(int(next_tok))
        self._stamp_first(req)
        self._just_admitted.add(req.slot)  # pipelined path merges it in
        fin = self._maybe_finish(req)
        if fin is not None:  # e.g. max_new_tokens == 1
            done[req.req_id] = fin

    def _admit_import(self, req: _Request, free: List[int],
                      done: Dict[int, List[int]]) -> bool:
        """Seat one KV-import request: match shared prompt pages against
        the LOCAL prefix cache (cross-replica reuse — only the
        non-shared context pages are spliced), allocate the rest, write
        the imported pages into the paged cache in one scatter
        (models/decoding.py splice_kv_pages), and arm the decode slot at
        the exported context.  Returns False on page backpressure (the
        request stays at the head of the queue)."""
        import jax.numpy as jnp

        from ray_tpu.models.decoding import splice_kv_pages

        bundle = req.kv_bundle
        L = len(req.prompt)
        ps = self.page_size
        ctx = int(bundle["context_len"])
        total = math.ceil((L + req.max_new_tokens) / ps)
        n_ctx = max(1, math.ceil(ctx / ps))
        full = L // ps
        shared: List[int] = []
        if self.prefix_cache is not None:
            if req.chain_keys is None:
                req.chain_keys = PrefixCache.chain_hashes(
                    req.prompt, ps, full)
            # Unlike fresh admission there is no (L-1) sampling cap:
            # the first token is already generated, so ALL full prompt
            # pages are reusable.
            shared = self.prefix_cache.match(req.chain_keys[:full])
            req.cache_keys = req.chain_keys[:len(shared)]
        n_shared = len(shared)
        n_private = total - n_shared
        if n_private > self._available_pages():
            if self.prefix_cache is not None and req.cache_keys:
                self.prefix_cache.release(req.cache_keys)
                req.cache_keys = []
            return False
        self.waiting.pop(0)
        self._note_admitted(req)
        slot = free.pop(0)
        req.slot = slot
        req.pages = self._alloc_evicting(n_private)
        pages = shared + req.pages
        table = np.zeros(self.max_pages_per_seq, dtype=np.int32)
        table[:len(pages)] = pages
        self.block_tables[slot] = table

        # Splice the non-shared context pages (pow-2 padded; -1 rows
        # drop in the scatter).  Pages 0..n_shared-1 already hold the
        # same KV locally via the prefix cache.
        n_splice = n_ctx - n_shared
        nbytes = 0
        if n_splice > 0:
            k = np.asarray(bundle["k"])[:, n_shared:n_ctx]
            v = np.asarray(bundle["v"])[:, n_shared:n_ctx]
            nbytes = k.nbytes + v.nbytes
            N = 1 << (n_splice - 1).bit_length()
            ids = np.full(N, -1, dtype=np.int32)
            ids[:n_splice] = pages[n_shared:n_ctx]
            kp = np.zeros((k.shape[0], N) + k.shape[2:], dtype=k.dtype)
            vp = np.zeros_like(kp)
            kp[:, :n_splice] = k
            vp[:, :n_splice] = v
            self.cache = splice_kv_pages(
                self.cache, jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(ids))

        # Adopt the request's full prompt pages into the local prefix
        # cache (now valid post-splice) so later requests sharing the
        # prefix hit locally — this is what makes prefix reuse survive
        # the replica boundary.
        if self.prefix_cache is not None and req.chain_keys:
            if shared:
                self.prefix_cache.hits += 1
                self.prefix_cache.tokens_saved += n_shared * ps
            own = []
            for i in range(n_shared, full):
                page = pages[i]
                if self.prefix_cache.register(req.chain_keys[i], page, i):
                    req.cache_keys.append(req.chain_keys[i])
                    own.append(page)
            req.pages = [p for p in req.pages if p not in own]

        self.context_lens[slot] = ctx
        self.last_tokens[slot] = req.generated[-1]
        self._stamp_first(req)  # splice done; tokens already exist
        self._just_admitted.add(slot)
        self.kv_imports += 1
        _KV_HANDOFF.inc(tags={"direction": "import"})
        _KV_HANDOFF_BYTES.inc(nbytes, tags={"direction": "import"})
        flight_recorder.record(
            "serve", "kv_import", req_id=req.req_id, pages=n_splice,
            shared_pages=n_shared, bytes=nbytes)
        req.kv_bundle = None  # release the page tensors
        _QUEUE_DEPTH.set(len(self.waiting))
        fin = self._maybe_finish(req)
        if fin is not None:
            done[req.req_id] = fin
        return True

    # -- packed async admission (greedy pipelined path) --------------------
    def _seg_len(self, prompt_len: int) -> int:
        """Pow-2 page-multiple bucket a prompt pads to inside a packed
        row (pow-2 >= page_size is automatically a page multiple)."""
        return max(self.page_size, 1 << (prompt_len - 1).bit_length())

    def _wave_eligible(self, req: "_Request") -> bool:
        """Packed admission serves greedy, prefix-cache-miss prompts;
        sampling needs host logits and cache hits need the gather-based
        chunked program — both stay on the classic path."""
        if not self.packed_admit or req.temperature > 0.0:
            return False
        if req.kv_bundle is not None:
            return False  # imported KV splices in via the classic path
        if self.prefix_cache is not None:
            L = len(req.prompt)
            if req.chain_keys is None:
                req.chain_keys = PrefixCache.chain_hashes(
                    req.prompt, self.page_size, L // self.page_size)
            matchable = max(0, (L - 1) // self.page_size)
            if self.prefix_cache.peek(req.chain_keys[:matchable]) > 0:
                return False
        return True

    def _dispatch_prefill_wave(self) -> int:
        """Admit a FIFO prefix of wave-eligible same-bucket requests in
        ONE async dispatch (models/decoding.py packed_prefill_admit):
        prompts pack into matmul-efficient rows, K/V pages are written,
        first greedy tokens computed, and the device decode state
        updated — without materializing anything on the host.  The
        first tokens surface at reconcile time, off the critical path,
        so in-flight decode chunks keep the device busy while prompts
        prefill."""
        if not self.packed_admit or not self._pipelined_ok():
            return 0
        free = self._free_slots()
        if not free or not self.waiting:
            return 0
        import jax.numpy as jnp

        from ray_tpu.models.decoding import packed_prefill_admit

        batch: List[_Request] = []
        head_sl = None
        budget = min(self.prefill_wave_tokens, self._step_prefill_left)
        budget0 = budget
        # Same-wave shared-prefix dedup (mirrors classic _admit's
        # pending_keys): a request whose prefix THIS wave will register
        # defers one step, then admits via the cache-hit classic path
        # instead of recomputing the prefix.
        pending_keys: set = set()
        while self.waiting and free:
            req = self.waiting[0]
            if not self._wave_eligible(req):
                break
            if req.chain_keys and req.chain_keys[0] in pending_keys:
                break
            L = len(req.prompt)
            sl = self._seg_len(L)
            if head_sl is None:
                head_sl = sl
            elif sl != head_sl:
                break  # next bucket gets its own wave next step
            if budget < sl and (batch or self.num_active
                                or self._inflight):
                # Budget spent this step (or too small for the bucket):
                # live decode work keeps the device; an idle engine
                # still admits the head so progress is never starved.
                break
            total = math.ceil((L + req.max_new_tokens) / self.page_size)
            if total > self._available_pages():
                break  # backpressure: wait for pages
            self.waiting.pop(0)
            self._note_admitted(req)
            req.slot = free.pop(0)
            req.pages = self._alloc_evicting(total)
            if self.prefix_cache is not None and req.chain_keys:
                pending_keys.update(
                    req.chain_keys[:L // self.page_size])
            batch.append(req)
            budget -= sl
        if not batch:
            return 0
        self._step_prefill_left = max(
            0, self._step_prefill_left - (budget0 - budget))
        # Fold pending host-side slot changes in BEFORE the wave slots
        # become live: a freed-slot merge arriving after assignment
        # would overwrite the wave's device-computed rows.
        self._sync_dstate()

        seg_len = head_sl
        ps = self.page_size
        segs_per_row = max(1, self.prefill_row_tokens // seg_len)
        rows = math.ceil(len(batch) / segs_per_row)
        R = 1 << (rows - 1).bit_length()
        S_row = segs_per_row * seg_len
        nseg = R * segs_per_row
        seg_pages = seg_len // ps
        tokens = np.zeros((R, S_row), dtype=np.int32)
        positions = np.full((R, S_row), -1, dtype=np.int32)
        row_tables = np.zeros((R, S_row // ps), dtype=np.int32)
        seg_slot = np.full(nseg, self.max_batch, dtype=np.int32)
        seg_limit = np.zeros(nseg, dtype=np.int32)
        seg_eos = np.full(nseg, -1, dtype=np.int32)
        for i, req in enumerate(batch):
            r, si = divmod(i, segs_per_row)
            L = len(req.prompt)
            j0 = si * seg_len
            tokens[r, j0:j0 + L] = req.prompt
            positions[r, j0:j0 + L] = np.arange(L)
            npg = min(len(req.pages), seg_pages)
            row_tables[r, si * seg_pages:si * seg_pages + npg] = \
                req.pages[:npg]
            seg_slot[i] = req.slot
            seg_limit[i] = L + req.max_new_tokens - 1
            seg_eos[i] = req.eos_token if req.eos_token is not None \
                else -1
            table = np.zeros(self.max_pages_per_seq, dtype=np.int32)
            table[:len(req.pages)] = req.pages
            self.block_tables[req.slot] = table
            self.context_lens[req.slot] = L
            self.slot_req[req.slot] = req
            # Wave slots are device-authoritative from here on; the
            # _sync_dstate() call above flushed any pending host-side
            # merge for them while they were still free, so no stale
            # host row can overwrite the wave's device-computed state.
            # Adopt full prompt pages immediately: later matches order
            # behind this dispatch through the device cache handle.
            if self.prefix_cache is not None and req.chain_keys:
                own = []
                for pi in range(L // ps):
                    page = req.pages[pi]
                    if self.prefix_cache.register(
                            req.chain_keys[pi], page, pi):
                        req.cache_keys.append(req.chain_keys[pi])
                        own.append(page)
                req.pages = [p for p in req.pages if p not in own]

        toks, pos, ctx, lim, eos = self._dstate
        first, self.cache, toks, pos, ctx, lim, eos = \
            packed_prefill_admit(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(row_tables), jnp.asarray(seg_slot),
                jnp.asarray(seg_limit), jnp.asarray(seg_eos),
                self.cache, toks, pos, ctx, lim, eos, self.config,
                seg_len)
        self._dstate = (toks, pos, ctx, lim, eos)
        self._inflight.append({
            "type": "prefill", "first": first, "segs": list(batch),
            "planned": {req.slot: 1 for req in batch}})
        self.waves_dispatched += 1
        return len(batch)

    def _eager_reconcile(self, done: Dict[int, List[int]]):
        """Fold in any in-flight records whose device results are
        already materialized — free TTFT/latency, no waiting."""
        while self._inflight:
            ch = self._inflight[0]
            arr = ch["first"] if ch.get("type") == "prefill" \
                else ch["out"]
            try:
                if not arr.is_ready():
                    break
            except AttributeError:
                break
            self._reconcile_oldest(done)

    # -- pipelined chunk decode (greedy multi-step) ------------------------
    def _slot_state_rows(self, slot: int):
        """Host-authoritative device-state row for one slot: live slots
        mirror the armed decode state; empty slots read as dead
        (pos=-1, ctx=0) so the device skips their attention and drops
        their writes."""
        req = self.slot_req[slot]
        if req is None:
            return 0, -1, 0, -1, -1
        cl = int(self.context_lens[slot])
        limit = len(req.prompt) + req.max_new_tokens - 1
        eos = req.eos_token if req.eos_token is not None else -1
        return int(self.last_tokens[slot]), cl, cl + 1, limit, eos

    def _sync_dstate(self):
        """Create or update the device-chained decode state.  A full
        rebuild only happens entering pipelined mode; afterwards host
        slot changes (admissions, frees) fold in via ONE masked-select
        dispatch (merge_slot_state) — never a device read-back."""
        import jax.numpy as jnp

        from ray_tpu.models.decoding import merge_slot_state

        B = self.max_batch
        if self._dstate is None:
            rows = [self._slot_state_rows(s) for s in range(B)]
            cols = list(zip(*rows))
            self._dstate = tuple(
                jnp.asarray(np.asarray(c, dtype=np.int32)) for c in cols)
            self._just_admitted.clear()
            self._dirty_slots.clear()
            return
        changed = self._just_admitted | self._dirty_slots
        if not changed:
            return
        mask = np.zeros(B, dtype=bool)
        new = np.zeros((5, B), dtype=np.int32)
        for s in changed:
            mask[s] = True
            new[:, s] = self._slot_state_rows(s)
        self._dstate = merge_slot_state(
            *self._dstate, jnp.asarray(mask), *map(jnp.asarray, new))
        self._just_admitted.clear()
        self._dirty_slots.clear()

    def _inflight_tokens(self, slot: int) -> int:
        """Upper bound on tokens already dispatched for a slot in
        chunks not yet reconciled."""
        return sum(ch["planned"].get(slot, 0) for ch in self._inflight)

    def _dispatch_chunk(self) -> bool:
        """Dispatch one multi_step decode chunk off the device-chained
        state.  Never blocks: inputs are the previous chunk's device
        arrays plus the (tiny) host block tables.  Returns False when
        every expected token is already in flight."""
        import jax.numpy as jnp

        from ray_tpu.models.decoding import decode_multi_step

        n = self.multi_step
        snapshot: Dict[int, _Request] = {}
        planned: Dict[int, int] = {}
        max_ub = 1
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            rem = (req.max_new_tokens - len(req.generated)
                   - self._inflight_tokens(slot))
            if rem > 0:
                snapshot[slot] = req
                planned[slot] = min(n, rem)
                # Furthest position this chunk can WRITE for the slot.
                max_ub = max(max_ub, int(self.context_lens[slot])
                             + self._inflight_tokens(slot) + min(n, rem))
        if not snapshot:
            return False
        self._sync_dstate()
        pages_needed = max(1, math.ceil(max_ub / self.page_size))
        W = min(self.max_pages_per_seq,
                1 << (pages_needed - 1).bit_length())
        tables = jnp.asarray(self.block_tables[:, :W])
        toks, pos, ctx, lim, eos = self._dstate
        out, toks, pos, ctx, self.cache = decode_multi_step(
            self.params, toks, self.cache, tables, pos, ctx, lim, eos,
            self.config, n)
        self._dstate = (toks, pos, ctx, lim, eos)
        self._inflight.append(
            {"out": out, "snapshot": snapshot, "planned": planned,
             "n": n})
        return True

    def _reconcile_oldest(self, done: Dict[int, List[int]]):
        """Materialize the oldest in-flight chunk's tokens (this is the
        only point the pipelined path waits on the device) and replay
        them into host state: append tokens, advance context mirrors,
        finish/free requests.  Rows for slots that died device-side
        (limit/EOS) carry -1 past the stop."""
        ch = self._inflight.pop(0)
        if ch.get("type") == "prefill":
            self.prefill_reconciles += 1
            first = np.asarray(ch["first"])
            for i, req in enumerate(ch["segs"]):
                if self.slot_req[req.slot] is not req:
                    continue
                tok = int(first[i])
                # Keep the host mirror authoritative: a mode switch to
                # the classic path (_flush_pipeline -> _decode) resumes
                # decoding from last_tokens.
                self.last_tokens[req.slot] = tok
                req.generated.append(tok)
                self._stamp_first(req)
                fin = self._maybe_finish(req)
                if fin is not None:
                    done[req.req_id] = fin
                    self._dirty_slots.add(req.slot)
            return
        toks = np.asarray(ch["out"])
        for slot, req in ch["snapshot"].items():
            if self.slot_req[slot] is not req:
                continue  # finished in an earlier chunk; rows are -1
            for j in range(ch["n"]):
                tok = int(toks[slot, j])
                if tok < 0:
                    break
                self.context_lens[slot] += 1
                self.last_tokens[slot] = tok
                req.generated.append(tok)
                fin = self._maybe_finish(req)
                if fin is not None:
                    done[req.req_id] = fin
                    # Zero the slot on device at the next merge so
                    # in-flight chunks' dead-slot attention stops
                    # burning bandwidth on freed pages.
                    self._dirty_slots.add(slot)
                    break

    def _flush_pipeline(self, done: Dict[int, List[int]]):
        """Drain every in-flight chunk and drop the device state (host
        mirrors become authoritative) — the classic path and mode
        switches run against host state."""
        while self._inflight:
            self._reconcile_oldest(done)
        self._dstate = None
        self._just_admitted.clear()
        self._dirty_slots.clear()

    def _draft_for(self, req: _Request, k: int) -> List[int]:
        """Prompt-lookup drafting (n-gram match): copy what followed the
        most recent earlier occurrence of the trailing n-gram. The
        n-gram -> latest-start index is maintained incrementally and the
        sequence is addressed through prompt/generated in place, so a
        step costs O(new_tokens * n + k) — no per-step list copies."""
        n = self.spec_ngram
        P = len(req.prompt)
        L = P + len(req.generated)
        if k <= 0 or L <= n:
            return []

        def tok(i: int) -> int:
            return req.prompt[i] if i < P else req.generated[i - P]

        # Index n-grams that have at least one continuation token
        # (ending at position <= L-2), from where we left off.
        for j in range(max(req.indexed_upto, n - 1), L - 1):
            gram = tuple(tok(j - n + 1 + t) for t in range(n))
            req.ngram_index[gram] = j - n + 1
        req.indexed_upto = max(req.indexed_upto, L - 1)
        tail = tuple(tok(L - n + t) for t in range(n))
        i = req.ngram_index.get(tail)
        if i is None:
            return []
        return [tok(p) for p in range(i + n, min(i + n + k, L))]

    def _spec_decode_batch(self, items: List[tuple]) -> Dict[int, int]:
        """Verify every eligible slot's [last_token, draft...] in ONE
        batched chunked forward; returns {slot: tokens_advanced} after
        updating slot state. Rejected positions still yield the model's
        own next token, so each slot advances by >= 1."""
        import jax.numpy as jnp

        from ray_tpu.models.decoding import verify_step

        B = len(items)
        # Every shape axis is pow-2 bucketed — B included — so
        # fluctuating eligibility doesn't recompile verify_step each
        # step (pad rows carry position -1: K/V writes dropped, logits
        # ignored).
        Bb = 1 << (B - 1).bit_length()
        n_chunks = [1 + len(d) for _, _, d in items]
        S = max(2, 1 << (max(n_chunks) - 1).bit_length())
        max_end = max(int(self.context_lens[s]) + n
                      for (s, _, _), n in zip(items, n_chunks))
        W = min(self.max_pages_per_seq, max(1, 1 << (
            math.ceil(max_end / self.page_size) - 1).bit_length()))
        tokens = np.zeros((Bb, S), dtype=np.int32)
        positions = np.full((Bb, S), -1, dtype=np.int32)
        tables = np.zeros((Bb, W), dtype=np.int32)
        for r, ((slot, req, draft), n_chunk) in enumerate(
                zip(items, n_chunks)):
            cl = int(self.context_lens[slot])
            tokens[r, 0] = self.last_tokens[slot]
            tokens[r, 1:n_chunk] = draft
            positions[r, :n_chunk] = np.arange(cl, cl + n_chunk)
            tables[r] = self.block_tables[slot][:W]
        logits, self.cache = verify_step(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            self.cache, jnp.asarray(tables), self.config)
        logits = np.asarray(logits)

        advanced: Dict[int, List[int]] = {}
        for r, ((slot, req, draft), n_chunk) in enumerate(
                zip(items, n_chunks)):
            preds = np.argmax(logits[r, :n_chunk], axis=-1)
            accepted: List[int] = []
            for i, d in enumerate(draft):
                if int(preds[i]) != d:
                    break
                accepted.append(d)
            # The model's token at the first mismatch (or after a full
            # acceptance) comes free from the same forward.
            new_tokens = accepted + [int(preds[len(accepted)])]
            # Rejected drafts' K/V sit beyond the new context length;
            # the attention mask hides them until overwritten.
            self.context_lens[slot] = \
                int(self.context_lens[slot]) + len(new_tokens)
            self.last_tokens[slot] = new_tokens[-1]
            self.spec_drafted += len(draft)
            self.spec_accepted += len(accepted)
            advanced[slot] = new_tokens
        self.spec_steps += 1
        return advanced

    def _decode(self) -> Dict[int, List[int]]:
        import jax.numpy as jnp

        done: Dict[int, List[int]] = {}
        spec_slots: set = set()
        if self.spec_k > 0:
            eligible = []
            for slot, req in enumerate(self.slot_req):
                if req is None or req.temperature > 0.0:
                    continue  # sampling needs the rejection-free path
                remaining = req.max_new_tokens - len(req.generated)
                if remaining < 2:
                    continue
                draft = self._draft_for(req,
                                        min(self.spec_k, remaining - 1))
                if draft:
                    eligible.append((slot, req, draft))
            if eligible:
                advanced = self._spec_decode_batch(eligible)
                for slot, req, _ in eligible:
                    spec_slots.add(slot)
                    for tok in advanced[slot]:
                        req.generated.append(tok)
                        fin = self._maybe_finish(req)
                        if fin is not None:
                            # EOS / max inside the accepted block:
                            # tokens past it are discarded.
                            done[req.req_id] = fin
                            break
            if all(r is None or s in spec_slots
                   for s, r in enumerate(self.slot_req)):
                return done

        active = np.array([
            r is not None and s not in spec_slots
            for s, r in enumerate(self.slot_req)])
        # Inactive slots get position -1: their K/V writes are dropped
        # (write_page_tokens) instead of landing in page 0 offset 0 via
        # their zeroed block tables — which would corrupt whichever
        # sequence owns page 0.
        positions = np.where(active, self.context_lens, -1).astype(np.int32)
        ctx = (self.context_lens + 1).astype(np.int32)
        # Bucket the table width to the longest live context (pow-2 for
        # compile reuse): the decode gather's HBM traffic is
        # O(B·W·page) PER LAYER, so passing the full max_seq_len-wide
        # tables made every step pay for contexts nobody had (measured
        # 15-20x step-time inflation at 2k max_seq_len / 256-token
        # contexts on v5e).  Greedy multi-step batches route through
        # the pipelined chunk path (_dispatch_chunk) before reaching
        # here; this classic step serves sampling/spec slots one token
        # at a time.
        pages_needed = max(1, math.ceil(int(ctx.max(initial=1))
                                        / self.page_size))
        W = min(self.max_pages_per_seq,
                1 << (pages_needed - 1).bit_length())
        tables = jnp.asarray(self.block_tables[:, :W])

        logits, self.cache = decode_step(
            self.params, jnp.asarray(self.last_tokens), self.cache,
            tables, jnp.asarray(positions),
            jnp.asarray(ctx), self.config)
        logits = np.asarray(logits)
        for slot, req in enumerate(self.slot_req):
            if req is None or slot in spec_slots:
                continue  # spec slots already advanced this step
            self.context_lens[slot] += 1
            tok = self._sample(logits[slot], req)
            self.last_tokens[slot] = tok
            req.generated.append(int(tok))
            fin = self._maybe_finish(req)
            if fin is not None:
                done[req.req_id] = fin
        return done

    def _sample(self, logits: np.ndarray, req: _Request) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        p = logits / req.temperature
        p = np.exp(p - p.max())
        p = p / p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _maybe_finish(self, req: _Request) -> Optional[List[int]]:
        """Register req into its slot, or retire it if done. Returns the
        generated tokens when finished."""
        hit_eos = (req.eos_token is not None
                   and req.generated
                   and req.generated[-1] == req.eos_token)
        if len(req.generated) >= req.max_new_tokens or hit_eos:
            if req.slot >= 0:
                if req.export_on_finish:
                    # Capture the KV pages before they are freed below:
                    # the prefill half of a disaggregated handoff.  ctx
                    # is derived from the invariant (KV written for the
                    # prompt + all generated tokens but the last) rather
                    # than context_lens, which can run ahead when a
                    # speculative block finishes early and discards its
                    # tail tokens.
                    ctx = len(req.prompt) + len(req.generated) - 1
                    self.kv_ready[req.req_id] = self._kv_bundle(
                        req, req.slot, ctx)
                    while len(self.kv_ready) > 32:
                        self.kv_ready.pop(next(iter(self.kv_ready)))
                self.slot_req[req.slot] = None
                self.context_lens[req.slot] = 0
                self.allocator.free(req.pages)
                if self.prefix_cache is not None and req.cache_keys:
                    # Shared/registered prompt pages stay cached
                    # (evictable once unreferenced).
                    self.prefix_cache.release(req.cache_keys)
            self._note_finished(req)
            self.num_completed += 1
            return req.generated
        self.slot_req[req.slot] = req
        return None
