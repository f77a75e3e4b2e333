"""Device-plane telemetry: backend probe, compile-event accounting,
HBM ledger, and continuous roofline/MFU attribution.

Everything here rides the existing observability transports — metric
registry snapshots, the span ring, the durable ops journal ("device"
stream), and the worker profile sampler — no new wire ops.

Design rules:
  - Never import jax on behalf of a process that has not already
    loaded it: ``device_sample()`` and ``backend_info()`` return the
    CPU/none fallback unless ``sys.modules`` already holds jax (the
    dashboard can opt into a forced probe with ``probe=True``).
  - Sampling must never hurt the caller: every probe is wrapped and
    degrades to None / empty on any backend quirk.
  - The compile hook detects recompiles by diffing the jitted
    callable's tracing-cache size around each call (``_cache_size()``
    where jax provides it, an argument-signature set otherwise), so
    it works identically under JAX_PLATFORMS=cpu — shape churn on a
    CPU host is the same bug as on a TPU host.
  - What a compile COST comes from JAX itself: one ``jax.monitoring``
    listener (``install_compile_listener``) turns every trace,
    lowering, backend-compile and persistent-cache event of the process
    into an ``xla.compile`` span and the ``compile_totals()``.
  - What a program IS comes from the program: the wrapper
    ``count_compiles`` returns keeps the abstract signature of its
    first call, and ``program_report(name)`` lowers and compiles that
    again (a hit in the persistent cache) for the compiled module's
    instructions, their scopes and its memory.  Nobody calls it on a
    hot path; ``TrainWorker.timeline()`` does, after a traced run.
"""

import contextlib
import logging
import os
import re
import sys
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core.log_once import warn_once

logger = logging.getLogger(__name__)

_FALSY = ("0", "false", "no", "off", "")

_lock = threading.Lock()

# name -> {"count", "after_warmup", "total_wall_s", "last_wall_s",
#          "last_shapes", "first_ts", "last_ts"}
_compiles: Dict[str, Dict[str, Any]] = {}

# component -> absolute device bytes attributed by the owning
# subsystem (weights / kv_pages / arena / ...).
_components: Dict[str, int] = {}

_watermark_bytes = 0
_watermark_fraction = 0.0
# the most device bytes a program said it needs to run (`note_program`)
_program_bytes = 0
_last_step: Optional[Dict[str, Any]] = None

_metrics_cache: Optional[Tuple[Any, Any, Any, Any]] = None


def _env_flag(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default).strip().lower() not in _FALSY


def _env_int(name: str, default: int, floor: int = 0) -> int:
    try:
        return max(floor, int(os.environ.get(name, str(default))))
    except ValueError:
        return default


def _env_float(name: str, default: float, floor: float = 0.0) -> float:
    try:
        return max(floor, float(os.environ.get(name, str(default))))
    except ValueError:
        return default


_enabled = _env_flag("RAY_TPU_DEVICE_STATS", "1")
_warmup = _env_int("RAY_TPU_DEVICE_RECOMPILE_WARMUP", 2, 0)


def set_enabled(on: bool) -> None:
    """Runtime switch for the compile hook + step accounting (the
    bench A/B phase and tests flip this without re-importing)."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Test hook: drop all per-process accumulated state."""
    global _watermark_bytes, _watermark_fraction, _last_step
    global _program_bytes
    with _lock:
        _compiles.clear()
        _first_calls.clear()
        _components.clear()
        _totals.update(_ZERO_TOTALS)
        _watermark_bytes = 0
        _watermark_fraction = 0.0
        _program_bytes = 0
        _last_step = None


# ---------------------------------------------------------------------------
# backend probe


def _jax():
    """The already-imported jax module, or None.  Deliberately does
    NOT import jax: a plain task worker that never touched jax must
    not pay a multi-second import inside its profile sampler."""
    return sys.modules.get("jax")


def backend_info(probe: bool = False) -> Dict[str, Any]:
    """{"backend", "device_kind", "num_devices"}.  backend is
    "unloaded" when jax was never imported here (unless probe=True,
    which imports it), and falls back to "cpu"/"none" on error."""
    jax = _jax()
    if jax is None and probe:
        try:
            import jax  # noqa: F811
        except Exception:
            return {"backend": "none", "device_kind": "", "num_devices": 0}
    if jax is None:
        return {"backend": "unloaded", "device_kind": "", "num_devices": 0}
    try:
        devs = jax.devices()
        d0 = devs[0]
        return {
            "backend": d0.platform,
            "device_kind": getattr(d0, "device_kind", d0.platform),
            "num_devices": len(devs),
        }
    except Exception:
        return {"backend": "none", "device_kind": "", "num_devices": 0}


def has_accelerator() -> bool:
    return backend_info().get("backend") not in (
        "cpu", "none", "unloaded", "")


def memory_stats(device=None) -> Optional[Dict[str, Any]]:
    """device.memory_stats() of `device` (device 0 where none is given),
    or None (CPU backends and older runtimes return None or raise, as
    does a device of another process — all degrade to None)."""
    jax = _jax()
    if jax is None:
        return None
    try:
        stats = (device or jax.devices()[0]).memory_stats()
        return dict(stats) if stats else None
    except Exception:  # raylint: allow-swallow(cpu/older runtimes raise here; None is the documented fallback)
        return None


# Peak rates of one chip by jax `device_kind`: (HBM bytes/s, dense bf16
# FLOP/s).  Source: Google Cloud TPU documentation, system-architecture
# pages for v4, v5e ("TPU v5 lite"), v5p and v6e ("TPU v6 lite").  A kind
# that is not here has no assumed peak — RAY_TPU_DEVICE_HBM_GBPS /
# RAY_TPU_DEVICE_PEAK_TFLOPS supply one explicitly (CPU hosts, tests).
PEAK_SPECS = {
    "TPU v4": (1228e9, 275e12),
    "TPU v5 lite": (819e9, 197e12),
    "TPU v5": (2765e9, 459e12),
    "TPU v5p": (2765e9, 459e12),
    "TPU v6 lite": (1640e9, 918e12),
}


def peak_specs_for(device_kind: str) -> Tuple[float, float]:
    """(hbm_bytes_per_s, peak_flops_per_s) of a known device kind; an
    unknown kind is a KeyError — benchmarks must not assume a peak."""
    try:
        return PEAK_SPECS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates recorded for device kind {device_kind!r}; "
            f"known: {sorted(PEAK_SPECS)}") from None


def peak_specs() -> Optional[Tuple[float, float]]:
    """(hbm_bytes_per_s, peak_flops_per_s) for the local backend, or
    None when its kind is unknown and no override supplies both."""
    hbm = _env_float("RAY_TPU_DEVICE_HBM_GBPS", 0.0) * 1e9
    tf = _env_float("RAY_TPU_DEVICE_PEAK_TFLOPS", 0.0) * 1e12
    if hbm and tf:
        return hbm, tf
    spec = PEAK_SPECS.get(backend_info().get("device_kind", ""))
    if spec is None:
        return None
    return (hbm or spec[0], tf or spec[1])


# ---------------------------------------------------------------------------
# metrics / journal (both lazy so importing this module stays free)


def _metrics():
    global _metrics_cache
    if _metrics_cache is None:
        from ray_tpu.util.metrics import Counter, Gauge
        _metrics_cache = (
            Counter("ray_tpu_recompiles_total",
                    "XLA compilations observed after per-function "
                    "warmup (recompile churn)", tag_keys=("function",)),
            Gauge("ray_tpu_device_roofline_fraction",
                  "Achieved / roofline HBM-bandwidth fraction of the "
                  "last sampled step window", tag_keys=("plane",)),
            Gauge("ray_tpu_device_mfu",
                  "Model FLOPs utilization of the last sampled step "
                  "window", tag_keys=("plane",)),
            Gauge("ray_tpu_device_hbm_watermark_fraction",
                  "Peak observed device-memory occupancy fraction "
                  "since process start"),
        )
    return _metrics_cache


def _journal(record: Dict[str, Any]) -> None:
    try:
        from ray_tpu.util import journal
        js = journal.stream("device")
        if js is not None:
            js.append(record)
    except Exception as exc:
        warn_once(logger, "device-journal", exc,
                  "could not append to the device journal stream")


# ---------------------------------------------------------------------------
# compile-event hook


def _arg_shapes(args: tuple, kwargs: dict) -> list:
    out = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            out.append([list(shape), str(getattr(a, "dtype", ""))])
        elif isinstance(a, (int, float, bool)):
            out.append(a)
        else:
            out.append(type(a).__name__)
    for k in sorted(kwargs):
        v = kwargs[k]
        shape = getattr(v, "shape", None)
        out.append([k, list(shape) if shape is not None
                    else type(v).__name__])
    return out


def note_compile(name: str, wall_s: float, shapes: list) -> None:
    """Record one observed compilation of `name`.  Past the warmup
    allowance the recompile counter increments and the event lands in
    the durable "device" journal stream."""
    now = time.time()
    with _lock:
        ent = _compiles.setdefault(name, {
            "count": 0, "after_warmup": 0, "total_wall_s": 0.0,
            "last_wall_s": 0.0, "last_shapes": None,
            "first_ts": now, "last_ts": now,
        })
        ent["count"] += 1
        ent["total_wall_s"] += wall_s
        ent["last_wall_s"] = wall_s
        ent["last_shapes"] = shapes
        ent["last_ts"] = now
        post_warmup = ent["count"] > _warmup
        if post_warmup:
            ent["after_warmup"] += 1
        count, after_warmup = ent["count"], ent["after_warmup"]
    if post_warmup:
        try:
            _metrics()[0].inc(tags={"function": name})
        except Exception as exc:
            warn_once(logger, "device-metrics", exc,
                      "could not update device metrics")
    _journal({"kind": "compile", "ts": now, "function": name,
              "wall_s": round(wall_s, 4), "shapes": shapes,
              "count": count, "after_warmup": after_warmup})


class _CompileTracked:
    """Wrapper around a jitted callable that counts compilations by
    diffing the tracing-cache size around each call.  Attribute access
    forwards to the wrapped function (``.lower``, AOT APIs, etc.)."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name
        self._seen_sigs = None  # fallback when _cache_size is absent
        # (args, kwargs, mesh) of the FIRST call, abstract: what
        # `program_report` lowers again.  Kept once, never per step.
        self._signature = None
        self.__wrapped__ = fn

    def _cache_size(self) -> int:
        try:
            return self._fn._cache_size()
        except Exception:
            return -1

    def __call__(self, *args, **kwargs):
        if not _enabled:
            return self._fn(*args, **kwargs)
        if self._signature is None:     # before the call: it may donate
            self._signature = _abstract_signature(args, kwargs)
            _first_calls[self._name] = self
        before = self._cache_size()
        if before < 0:
            # No tracing-cache introspection: fall back to tracking
            # coarse argument signatures (top-level shapes/dtypes).
            sig = tuple(
                (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", "")))
                if hasattr(a, "shape") else repr(a)[:64]
                for a in args)
            if self._seen_sigs is None:
                self._seen_sigs = set()
            miss = sig not in self._seen_sigs
            self._seen_sigs.add(sig)
            t0 = time.perf_counter()
            out = self._fn(*args, **kwargs)
            if miss:
                note_compile(self._name, time.perf_counter() - t0,
                             _arg_shapes(args, kwargs))
            return out
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if self._cache_size() > before:
            note_compile(self._name, time.perf_counter() - t0,
                         _arg_shapes(args, kwargs))
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def count_compiles(fn, name: Optional[str] = None):
    """Wrap a jitted callable so every (re)compilation is counted per
    function with shapes + wall time.  Transparent to callers."""
    label = name or getattr(fn, "__name__", None) or repr(fn)
    install_compile_listener()
    return _CompileTracked(fn, label)


def compile_counts() -> Dict[str, Dict[str, Any]]:
    """Per-function compile table (copies, json-safe)."""
    with _lock:
        return {k: dict(v) for k, v in _compiles.items()}


def recompiles_after_warmup() -> Dict[str, int]:
    """{function: compiles beyond the warmup allowance} — the compact
    form piggybacked on profile samples for the head-side watchdog."""
    with _lock:
        return {k: v["after_warmup"] for k, v in _compiles.items()
                if v["after_warmup"]}


# ---------------------------------------------------------------------------
# program report: a compiled program's instructions, scopes and memory

# name -> the wrapper of that name whose first call came last
_first_calls: "weakref.WeakValueDictionary[str, _CompileTracked]" = \
    weakref.WeakValueDictionary()


def _abstract_signature(args: tuple, kwargs: dict):
    """(args, kwargs, mesh) of a call with every array replaced by its
    shape, dtype and sharding, and the mesh `jax.sharding.set_mesh` had
    set around it (None where none was)."""
    jax = _jax()

    def abstract(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        return a

    mesh = jax.sharding.get_mesh()
    return (jax.tree.map(abstract, args), jax.tree.map(abstract, kwargs),
            None if mesh.empty else mesh)


# Instructions a trace never shows as an operation with time of its own.
_NO_EVENT = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                       "bitcast"))
_MATMULS = frozenset(("convolution", "dot"))
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
# what an instruction runs as operations of the same stream
_BODIES = re.compile(r"\b(?:condition|body|true_computation|"
                     r"false_computation|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")


def _after_type(rest: str) -> str:
    """An instruction's text behind its result type: a type has no blank
    outside its brackets (`bf16[8,128]{1,0:T(8,128)(2,1)}`, `(f32[8], ..)`)."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i:]
    return ""


def hlo_instructions(text: str) -> Tuple[str, Dict[str, list]]:
    """(module name, {instruction: [opcode, op_name, holds_matmul,
    custom_call_target]}) of a compiled module's text, for the
    instructions a device trace can show: those of the entry computation
    and of what `while`, `conditional` and `call` run, not the inside of
    fusions.  `op_name` is the metadata's (the named scopes are in it),
    `holds_matmul` whether the instruction is a matmul or a fusion that
    holds one."""
    module = text.split(None, 2)[1].rstrip(",") if text.startswith(
        "HloModule ") else ""
    comps: Dict[str, List[tuple]] = {}
    entry, current = None, None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY "):
                    entry = m.group(1)
            continue
        if line == "}":
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            tail = _after_type(m.group(2))
            op = _OPCODE.match(tail)
            current.append((m.group(1), op.group(1) if op else "", tail))
    matmul_in = {name: any(op in _MATMULS for _, op, _ in insts)
                 for name, insts in comps.items()}
    out: Dict[str, list] = {}
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, op, tail in comps[comp]:
            if op in ("while", "conditional", "call"):
                todo += _BODIES.findall(tail)
                for group in _BRANCHES.findall(tail):
                    todo += [b.strip().lstrip("%") for b in group.split(",")]
            if op in _NO_EVENT:
                continue
            fused = _FUSED.search(tail) if op == "fusion" else None
            op_name = _OP_NAME.search(tail)
            target = _TARGET.search(tail)
            out[name] = [
                op, op_name.group(1) if op_name else "",
                op in _MATMULS
                or bool(fused and matmul_in.get(fused.group(1))),
                target.group(1) if target else ""]
    return module, out


def program_bytes(memory) -> Optional[int]:
    """What a chip must hold to run a compiled program, from its
    `memory_analysis()`: arguments + outputs - aliased + temporaries
    (None where the backend gives no analysis)."""
    if memory is None:
        return None
    return int(memory.argument_size_in_bytes + memory.output_size_in_bytes
               - memory.alias_size_in_bytes + memory.temp_size_in_bytes)


def program_report(name: str) -> Optional[Dict[str, Any]]:
    """What the program counted as `name` (``count_compiles``) compiled
    to: its first call's abstract signature lowered and compiled again,
    which the persistent cache serves where the run filled it.  ->
    {"module": the module's name as a trace prints it, "instructions":
    `hlo_instructions`' table, "memory": `memory_analysis()` in bytes a
    device (arguments, outputs, temporaries, aliased, code, and `total`,
    what a chip must hold: arguments + outputs - aliased + temporaries),
    "bytes_limit": the device's (None where the backend reports none),
    "seconds": what making the report took}; None for a program that was
    never called here.  Lowers and compiles: for after a run, not in one."""
    tracked = _first_calls.get(name)
    jax = _jax()
    if tracked is None or jax is None:
        return None
    t0 = time.perf_counter()
    args, kwargs, mesh = tracked._signature
    with (jax.sharding.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        compiled = tracked._fn.lower(*args, **kwargs).compile()
    module, instructions = hlo_instructions(compiled.as_text())
    m = compiled.memory_analysis()
    memory = None
    if m is not None:
        memory = {"argument_bytes": int(m.argument_size_in_bytes),
                  "output_bytes": int(m.output_size_in_bytes),
                  "temp_bytes": int(m.temp_size_in_bytes),
                  "alias_bytes": int(m.alias_size_in_bytes),
                  "generated_code_bytes": int(
                      m.generated_code_size_in_bytes),
                  "total_bytes": program_bytes(m)}
    return {"module": module, "instructions": instructions,
            "memory": memory,
            "bytes_limit": (memory_stats() or {}).get("bytes_limit"),
            "seconds": time.perf_counter() - t0}


# ---------------------------------------------------------------------------
# compile cost: JAX's own monitoring events

# event name -> the `event` attribute of its xla.compile span
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
# A program the persistent cache could hold, compiled and written to it.
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_ZERO_TOTALS = {"compiles": 0, "cache_hits": 0, "cache_misses": 0,
                "compile_s": 0.0, "cache_retrieval_s": 0.0,
                "trace_lower_s": 0.0}
_totals: Dict[str, Any] = dict(_ZERO_TOTALS)
_listener_installed = False
_thread = threading.local()


# Trace and lowering events shorter than this get no span of their own
# (every eager primitive traces for microseconds); the totals count all.
_MIN_TRACE_SPAN_S = 1e-3


def _outer_seconds(secs: float, end: float) -> float:
    """Trace and lowering events nest: a jit traced inside another
    reports its own duration, then the outer one reports both.  Of an
    event that ended at `end`, the seconds not already reported by events
    inside it on this thread, so that the total is the union."""
    start = end - secs
    seen = getattr(_thread, "intervals", None)
    if seen is None:
        seen = _thread.intervals = []
    inner = 0.0
    while seen and seen[-1][0] >= start - 1e-4:
        inner += seen.pop()[1]
    seen.append((start, secs))
    del seen[:-64]
    return max(0.0, secs - inner)


def _on_compile_event(name: str, secs: float, **kw) -> None:
    kind = _COMPILE_EVENTS.get(name)
    if kind is None or not _enabled:
        return
    end = time.time()
    hit = kind == "cache_retrieval"
    with _lock:
        if hit:
            # JAX reports the retrieval inside the backend-compile event
            # it served, on the same thread: remember it for that one.
            _totals["cache_hits"] += 1
            _totals["cache_retrieval_s"] += secs
            _thread.retrieved_s = secs
        elif kind == "backend_compile":
            retrieved = getattr(_thread, "retrieved_s", None)
            _thread.retrieved_s = None
            hit = retrieved is not None
            _totals["compiles"] += 1
            _totals["compile_s"] += max(0.0, secs - (retrieved or 0.0))
        else:
            _totals["trace_lower_s"] += _outer_seconds(secs, end)
            if secs < _MIN_TRACE_SPAN_S:
                return
    try:
        from ray_tpu.util import tracing

        tracing.record_span(
            "xla.compile", end - secs, end, force=True,
            attributes={"event": kind, "seconds": secs, "cache_hit": hit,
                        "program": tracing.current_span_name() or "",
                        "fun_name": str(kw.get("fun_name", ""))})
    except Exception as exc:
        warn_once(logger, "device-compile-span", exc,
                  "could not record an xla.compile span")


def _on_cache_event(name: str, **kw) -> None:
    if name == _CACHE_MISS_EVENT and _enabled:
        with _lock:
            _totals["cache_misses"] += 1


def install_compile_listener() -> bool:
    """Register the process's one pair of ``jax.monitoring`` listeners,
    once, if JAX is already loaded here (never imports it).  Called by
    whatever first learns that the process has JAX: ``count_compiles``,
    a ``tracing.trace_span``, ``compile_totals``."""
    global _listener_installed
    mon = sys.modules.get("jax.monitoring")
    if mon is None or _listener_installed:
        return _listener_installed
    with _lock:
        if _listener_installed:
            return True
        _listener_installed = True
    mon.register_event_duration_secs_listener(_on_compile_event)
    mon.register_event_listener(_on_cache_event)
    return True


def compile_totals() -> Dict[str, Any]:
    """Process totals since start: ``compiles`` (backend compile events,
    served from the persistent cache or not), ``cache_hits`` (those that
    were), ``cache_misses`` (programs compiled and written to the cache:
    0 in a warm run), and the seconds spent compiling (``compile_s``),
    loading from the cache (``cache_retrieval_s``) and tracing + lowering
    (``trace_lower_s``)."""
    install_compile_listener()
    with _lock:
        return dict(_totals)


# ---------------------------------------------------------------------------
# HBM ledger


def attribute(component: str, nbytes: int) -> None:
    """Set the absolute device bytes attributed to `component`
    (weights / kv_pages / arena / ...).  Owners call this once at
    allocation time or per sampler tick; idempotent."""
    with _lock:
        _components[component] = int(nbytes)


def note_program(nbytes: int) -> None:
    """The device bytes a program needs while it runs, with what the
    device holds beside it: its `memory_analysis` total (`program_bytes`:
    the TEMPORARIES too, which the allocator's `peak_bytes_in_use` leaves
    out) plus the rest in use.  Whoever compiles a program and reads that
    says so once, then, not a step (`ShardedTrainStep`'s first step); the
    ledger's watermark is the larger of the most said here and the
    allocator's peak."""
    global _program_bytes
    with _lock:
        _program_bytes = max(_program_bytes, int(nbytes))


def ledger(probe: bool = False) -> Dict[str, Any]:
    """The per-process HBM ledger.  ALWAYS returns a dict (CPU hosts
    get backend="cpu" with capacity from the attribution sum), so the
    dashboard renders the same shape everywhere."""
    global _watermark_bytes, _watermark_fraction
    info = backend_info(probe=probe)
    stats = memory_stats()
    with _lock:
        components = dict(_components)
    attributed = sum(components.values())
    if stats:
        used = int(stats.get("bytes_in_use", attributed))
        capacity = int(stats.get("bytes_limit", 0)) or used
        # a running program's temporaries are in no allocator figure
        peak = max(int(stats.get("peak_bytes_in_use", used)), _program_bytes)
    else:
        used = attributed
        capacity = _env_int("RAY_TPU_DEVICE_HBM_BYTES", 0) or used
        peak = used
    workspace = max(0, used - attributed)
    with _lock:
        if peak > _watermark_bytes:
            _watermark_bytes = peak
        if capacity:
            frac = _watermark_bytes / capacity
            if frac > _watermark_fraction:
                _watermark_fraction = frac
        wm_bytes, wm_frac = _watermark_bytes, _watermark_fraction
    try:
        _metrics()[3].set(wm_frac)
    except Exception as exc:
        warn_once(logger, "device-metrics", exc,
                  "could not update device metrics")
    return {
        "backend": info["backend"],
        "device_kind": info["device_kind"],
        "num_devices": info["num_devices"],
        "capacity_bytes": capacity,
        "used_bytes": used,
        "watermark_bytes": wm_bytes,
        "watermark_fraction": round(wm_frac, 4),
        "components": components,
        "workspace_bytes": workspace,
        "memory_stats": stats,
        "ts": time.time(),
    }


# ---------------------------------------------------------------------------
# continuous roofline / MFU step hook


def note_step(*, tokens_per_s: float, bytes_per_token: float,
              flops_per_token: float, plane: str = "serve",
              extra: Optional[Dict[str, Any]] = None,
              ) -> Tuple[float, float]:
    """Fold one sampled step window into the continuous gauges.

    `bytes_per_token` / `flops_per_token` are the MODELED per-token
    traffic and compute (same terms bench_decode uses offline:
    weights + live KV for bytes, 2*params for flops).  Returns
    (roofline_fraction, mfu) — (None, None), and no fraction in the
    step record or the gauges, when the device's peaks are unknown."""
    global _last_step
    if not _enabled:
        return 0.0, 0.0
    step = {
        "kind": "step", "ts": time.time(), "plane": plane,
        "tokens_per_s": round(tokens_per_s, 2),
        "bytes_per_token": int(bytes_per_token),
        "flops_per_token": int(flops_per_token),
    }
    frac = mfu = None
    specs = peak_specs()
    if specs is not None:
        peak_bw, peak_flops = specs
        frac = tokens_per_s * max(0.0, bytes_per_token) / peak_bw
        mfu = tokens_per_s * max(0.0, flops_per_token) / peak_flops
        step["roofline_fraction"] = round(frac, 5)
        step["mfu"] = round(mfu, 5)
    if extra:
        step.update(extra)
    with _lock:
        _last_step = step
    if specs is not None:
        try:
            m = _metrics()
            m[1].set(frac, tags={"plane": plane})
            m[2].set(mfu, tags={"plane": plane})
        except Exception as exc:
            warn_once(logger, "device-metrics", exc,
                      "could not update device metrics")
    _journal(step)
    return frac, mfu


def last_step() -> Optional[Dict[str, Any]]:
    with _lock:
        return dict(_last_step) if _last_step else None


# ---------------------------------------------------------------------------
# profile-sampler piggyback


def device_sample() -> Optional[Dict[str, Any]]:
    """Device fields for the worker profile sampler.  None on hosts
    without an accelerator (JAX_PLATFORMS=cpu emits device: null —
    never raises), a compact ledger view otherwise."""
    try:
        if not has_accelerator():
            return None
        led = ledger()
        return {
            "backend": led["backend"],
            "device_kind": led["device_kind"],
            "capacity_bytes": led["capacity_bytes"],
            "used_bytes": led["used_bytes"],
            "watermark_fraction": led["watermark_fraction"],
            "components": led["components"],
            "workspace_bytes": led["workspace_bytes"],
        }
    except Exception:  # raylint: allow-swallow(sampling must never hurt the worker; None is the cpu/no-device value)
        return None


def profile_fields() -> Dict[str, Any]:
    """Top-level sample fields the worker sampler merges in: always
    includes "device" (possibly None); recompile counts and the last
    roofline/MFU window only when present, so the PR-6 history rings
    grow percentiles for them for free."""
    out: Dict[str, Any] = {"device": device_sample()}
    try:
        rec = recompiles_after_warmup()
        if rec:
            out["recompiles"] = rec
        ls = last_step()
        if ls:
            for key in ("roofline_fraction", "mfu", "tokens_per_s"):
                if key in ls:  # no fraction when the peaks are unknown
                    out[key] = ls[key]
        led_frac = _watermark_fraction
        if led_frac:
            out["hbm_watermark_fraction"] = round(led_frac, 4)
    except Exception as exc:
        warn_once(logger, "device-profile-fields", exc,
                  "could not build device profile fields")
    return out
