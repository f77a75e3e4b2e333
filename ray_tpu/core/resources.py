"""Resource model: named resource vectors with fractional amounts.

Counterpart of the reference's ResourceSet / NodeResources
(src/ray/common/scheduling/cluster_resource_data.h) with FixedPoint
arithmetic (fixed_point.h): amounts are stored as integer ten-thousandths so
fractional resources (0.5 CPU) compose exactly.

TPU-native extension (SURVEY.md §2 directive for N10): ``TPU`` is a
first-class resource alongside CPU/memory, and nodes may expose ICI-topology
markers (``TPU-v5e-8-head``, slice labels) the scheduler uses for
slice-aware placement, generalizing the reference's Python-side TPU
accelerator manager (python/ray/_private/accelerators/tpu.py).
"""

from __future__ import annotations

from typing import Dict, Mapping

GRANULARITY = 10_000  # fixed-point denominator

CPU = "CPU"
TPU = "TPU"
MEMORY = "memory"
OBJECT_STORE_MEMORY = "object_store_memory"


def _to_fixed(amount: float) -> int:
    return round(amount * GRANULARITY)


def _from_fixed(units: int) -> float:
    return units / GRANULARITY


class ResourceSet:
    """Immutable-ish mapping of resource name -> fixed-point amount."""

    __slots__ = ("_units",)

    def __init__(self, amounts: Mapping[str, float] | None = None, _units=None):
        if _units is not None:
            self._units: Dict[str, int] = {k: v for k, v in _units.items() if v > 0}
        else:
            self._units = {
                k: _to_fixed(v) for k, v in (amounts or {}).items() if v > 0
            }

    def to_dict(self) -> Dict[str, float]:
        return {k: _from_fixed(v) for k, v in self._units.items()}

    def get(self, name: str) -> float:
        return _from_fixed(self._units.get(name, 0))

    def is_empty(self) -> bool:
        return not self._units

    def is_subset_of(self, other: "ResourceSet") -> bool:
        return all(other._units.get(k, 0) >= v for k, v in self._units.items())

    def fit_count(self, need: "ResourceSet") -> int:
        """How many disjoint copies of `need` fit inside this set."""
        if not need._units:
            return 1 << 30
        return min(self._units.get(k, 0) // v
                   for k, v in need._units.items())

    def add(self, other: "ResourceSet") -> "ResourceSet":
        units = dict(self._units)
        for k, v in other._units.items():
            units[k] = units.get(k, 0) + v
        return ResourceSet(_units=units)

    def subtract(self, other: "ResourceSet") -> "ResourceSet":
        units = dict(self._units)
        for k, v in other._units.items():
            units[k] = units.get(k, 0) - v
            if units[k] < 0:
                raise ValueError(
                    f"Resource {k} would go negative: {self.to_dict()} - {other.to_dict()}"
                )
        return ResourceSet(_units=units)

    def __eq__(self, other):
        return isinstance(other, ResourceSet) and other._units == self._units

    def __repr__(self):
        return f"ResourceSet({self.to_dict()})"

    def __reduce__(self):
        return (ResourceSet, (self.to_dict(),))


def node_resources_from_env(num_cpus=None, num_tpus=None, extra=None) -> ResourceSet:
    """Detect this host's resources (CPU count, TPU chips if visible,
    accelerator pod-type markers like TPU-v4-16 / TPU-v4-16-head)."""
    import os

    amounts: Dict[str, float] = {}
    amounts[CPU] = float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    if num_tpus is None:
        num_tpus = detect_tpu_chips()
    if num_tpus:
        amounts[TPU] = float(num_tpus)
        try:
            from ray_tpu.accelerators import detect_additional_resources

            amounts.update(detect_additional_resources())
        except Exception:
            pass
    if extra:
        amounts.update(extra)
    return ResourceSet(amounts)


def visible_tpu_chip_ids() -> Optional[list]:
    """Chip ids assigned via env (TPU_VISIBLE_CHIPS / RAY_TPU_CHIPS),
    None when no env override is present.  Single source of the parsing
    shared by the scheduler (detect_tpu_chips) and the worker-facing
    get_accelerator_ids()."""
    import os

    env = os.environ.get("TPU_VISIBLE_CHIPS") or os.environ.get("RAY_TPU_CHIPS")
    if not env:
        return None  # unset/empty: caller falls back to device probing
    if env == "none":
        return []
    return [c for c in env.split(",") if c != ""]


def detect_tpu_chips() -> int:
    """Count locally visible TPU chips without initializing a JAX backend.

    Counterpart of the reference's TPU accelerator manager chip probing
    (python/ray/_private/accelerators/tpu.py:71): check the PCI accel
    device nodes and TPU_VISIBLE_CHIPS-style env overrides rather than
    importing jax (which would grab the chips).
    """
    import os

    ids = visible_tpu_chip_ids()
    if ids is not None:
        return len(ids)
    # accel / vfio device nodes, one per chip (a v5e host shows
    # /dev/vfio/<n> beside the /dev/vfio/vfio control node)
    for pattern_dir, prefix in (("/dev", "accel"), ("/dev/vfio", "")):
        try:
            entries = os.listdir(pattern_dir)
        except OSError:
            continue
        n = len([e for e in entries if e.startswith(prefix) and e[len(prefix):].isdigit()])
        if n:
            return n
    return 0
