"""What every cell shares: resolving a cell from BENCHMARK.json to its
files, loading the layer-metric readers, percentiles, and the last line.

Nothing here touches JAX: the process that runs `benchmark/run.py` never
initialises a backend (a parent that has touched JAX holds the chip, and
the worker that needs it then fails or hangs).
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# Everything a run leaves behind goes here (listed in .gitignore):
# traces, train results.  The compile cache keeps the program's own
# fixed path, <checkout>/.jax_cache (ray_tpu/util/compile_cache.py).
OUT_DIR = os.path.join(ROOT, ".bench_out")


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result."""


def say(phase: str, **facts) -> None:
    """An earlier line: progress, medians, sample counts.  Only the LAST
    line of stdout is the result."""
    print(json.dumps({"phase": phase, **facts}, default=str), flush=True)


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchFailure(f"no BENCHMARK.json at {path}")
    with open(path) as f:
        return json.load(f)


def resolve_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration file and its traffic mix."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"]
                           if "workloads" not in m
                           or workload in m["workloads"]],
            "per_layer": [m for m in bench["per_layer"]
                          if "workloads" not in m
                          or workload in m["workloads"]]}


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, root: str = ROOT):
    """A configuration names its driver; found by name under drivers/."""
    path = os.path.join(root, "benchmark", "drivers", name + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"configuration names driver {name!r}: no {path}")
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module(f"benchmark.drivers.{name}")


def load_generator(kind: str, root: str = ROOT):
    """A traffic mix names its generator; found by name under generators/.
    What a generator has to offer is set by the driver that consumes it
    (`drivers/train_step.py` wants `plan` and `batches`); the harness only
    finds the file."""
    path = os.path.join(root, "benchmark", "generators", kind + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"mix names generator {kind!r}: no {path}")
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module(f"benchmark.generators.{kind}")


def load_layer_metrics(root: str = ROOT) -> Dict[str, Any]:
    """Every reader under layer_metrics/, by the NAME it declares."""
    out = {}
    folder = os.path.join(root, "benchmark", "layer_metrics")
    for fn in sorted(os.listdir(folder)):
        if not fn.endswith(".py") or fn.startswith("_"):
            continue
        mod = load_module(os.path.join(folder, fn),
                          "layer_metric_" + fn[:-3].replace(".", "_"))
        out[mod.NAME] = mod
    return out


def read_layer_metrics(resolved: dict, spans: list, trace, counters: dict,
                       root: str = ROOT) -> Dict[str, dict]:
    """Run every reader this cell carries.  A reader that finds nothing
    to read returns None and its metric is left out of the line."""
    readers = load_layer_metrics(root)
    out = {}
    for m in resolved["per_layer"]:
        mod = readers.get(m["name"])
        if mod is None:
            say("layer_metric.missing_reader", name=m["name"])
            continue
        try:
            value = mod.read(spans, trace, counters, resolved)
        except Exception as e:  # noqa: BLE001 — one reader must not sink the line
            say("layer_metric.error", name=m["name"],
                error=f"{type(e).__name__}: {e}")
            continue
        if value is None or (isinstance(value, float)
                             and not math.isfinite(value)):
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, q in [0, 100]."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def model_kwargs(model: dict, max_seq_len: int) -> dict:
    """The program's TransformerConfig arguments from a configuration
    file's `model` group (the published key names)."""
    return dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim"), max_seq_len=max_seq_len,
        rope_theta=float(model["rope_theta"]),
        rms_eps=float(model["rms_norm_eps"]))


def peak_bytes(devices) -> int:
    """`peak_bytes_in_use` on the fullest of the worker's devices, as JAX
    reports it (0 where the backend reports nothing)."""
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:  # noqa: BLE001 — CPU backends raise
            pass
    return peak


def device_record(dev: dict, memory_peak_bytes: int) -> dict:
    return {"platform": dev["platform"], "kind": dev["kind"],
            "count": int(dev["count"]),
            "memory_peak_bytes": int(memory_peak_bytes)}


def check_device(dev: dict, chips: int, rehearse: bool) -> None:
    """A run that finds no TPU, or fewer chips than the cell asks for,
    fails; it never falls back."""
    if rehearse:
        return
    if dev["platform"] != "tpu":
        raise BenchFailure(f"the worker runs on {dev['platform']!r}, "
                           f"not on a TPU")
    if int(dev["count"]) < chips:
        raise BenchFailure(f"the worker sees {dev['count']} chips, the "
                           f"cell needs {chips}")


def kernels_ok(kernels: dict, must_take: List[str]) -> Optional[str]:
    """None if every main-path op took its Pallas kernel and nothing ran
    interpreted; else what is wrong."""
    for op in must_take:
        paths = kernels.get(op, {})
        if paths.get("pallas", 0) <= 0:
            return f"{op} never took its Pallas kernel: {kernels}"
        if set(paths) != {"pallas"}:
            return f"{op} also ran off the kernel: {paths}"
    for op, paths in kernels.items():
        if "interpret" in paths:
            return f"{op} ran interpreted: {paths}"
    return None


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict,
                breakdown: Optional[dict] = None) -> str:
    doc = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    return json.dumps(doc)
