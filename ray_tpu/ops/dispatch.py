"""Which implementation each op took: the one place that asks.

The Pallas kernels run natively on TPU; off TPU they run only when
RAY_TPU_PALLAS_INTERPRET=1 asks for the interpreter (kernel-semantics
tests), and the ops otherwise take their XLA formulation.  Every
dispatch decision is recorded at trace time so a caller (LLMServer
stats, chip_smoke.py) can check that the main path really took the
kernels instead of trusting that it did.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

import jax

_taken: Dict[str, Dict[str, int]] = {}
_lock = threading.Lock()  # engine and request threads both trace


def platform() -> str:
    """The default backend's platform.  A backend that fails to come up
    raises here: it must not be mistaken for a CPU."""
    return jax.default_backend()


def interpret_mode() -> bool:
    return os.environ.get("RAY_TPU_PALLAS_INTERPRET", "") in ("1", "true")


def record(op: str, path: str) -> None:
    """Note that `op` was traced down `path` ("pallas", "interpret" or
    "xla")."""
    with _lock:
        by_path = _taken.setdefault(op, {})
        by_path[path] = by_path.get(path, 0) + 1


def taken() -> Dict[str, Dict[str, int]]:
    """{op: {path: times traced}} for this process."""
    with _lock:
        return {op: dict(paths) for op, paths in _taken.items()}
