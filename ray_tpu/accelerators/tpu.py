"""TPU accelerator manager: chips, pod type, topology, slice resources.

Counterpart of the reference's python/ray/_private/accelerators/tpu.py
(:71 chip probing, :48 GCE metadata, :141 chips-per-host validation,
:334 pod-type resources + `TPU-{type}-head` marker). Detection order is
env vars → device nodes → (optionally) the GCE metadata server with a
short timeout, so it works on TPU VMs and in CPU test environments
without hanging anywhere.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from ray_tpu.accelerators.accelerator import AcceleratorManager
from ray_tpu.core.resources import detect_tpu_chips

_GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1"
# Valid requests are 1 chip (sub-host), a full host (usually 4), or the
# whole slice via the pod resource — same rule the reference validates.
_VALID_SUBHOST = (1.0, 2.0, 4.0, 8.0)


def _gce_metadata(path: str, timeout: float = 0.3) -> Optional[str]:
    """Best-effort GCE metadata probe (reference tpu.py:48). Returns None
    fast when not on GCE (zero-egress test/dev environments)."""
    if os.environ.get("RAY_TPU_NO_METADATA", "0") == "1":
        return None
    try:
        import urllib.request

        req = urllib.request.Request(
            f"{_GCE_METADATA_URL}/{path}",
            headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.read().decode()
    except Exception:
        return None


class TPUAcceleratorManager(AcceleratorManager):
    resource_name = "TPU"

    # -- detection ---------------------------------------------------------
    def get_num_accelerators(self) -> int:
        return detect_tpu_chips()

    def get_accelerator_type(self) -> Optional[str]:
        """Pod type like "v4-16" / "v5p-8": env override first
        (TPU_ACCELERATOR_TYPE on TPU VMs), then GCE metadata."""
        env = os.environ.get("TPU_ACCELERATOR_TYPE") \
            or os.environ.get("RAY_TPU_ACCELERATOR_TYPE")
        if env:
            return env
        return _gce_metadata("instance/attributes/accelerator-type")

    def get_topology(self) -> Optional[str]:
        """Physical topology like "2x2x2" (env TPU_TOPOLOGY or metadata)."""
        return os.environ.get("TPU_TOPOLOGY") \
            or _gce_metadata("instance/attributes/topology")

    def get_worker_id(self) -> int:
        """This host's index within its slice (0 = slice head)."""
        for key in ("TPU_WORKER_ID", "CLOUD_TPU_TASK_ID"):
            v = os.environ.get(key)
            if v is not None and v.isdigit():
                return int(v)
        v = _gce_metadata("instance/attributes/agent-worker-number")
        return int(v) if v and v.isdigit() else 0

    def get_slice_name(self) -> str:
        """Slice/pod identity for grouping hosts of one ICI domain."""
        return os.environ.get("TPU_NAME") \
            or _gce_metadata("instance/attributes/instance-id") or ""

    # -- resources ---------------------------------------------------------
    def get_additional_resources(self) -> Dict[str, float]:
        """Pod-type resources (reference tpu.py:334): every host of a
        v4-16 slice advertises `TPU-v4-16` = local chips so whole-slice
        placement groups can reserve by type, and worker 0 adds the
        `TPU-v4-16-head` marker used to anchor one driver per slice."""
        chips = self.get_num_accelerators()
        if not chips:
            return {}
        acc_type = self.get_accelerator_type()
        if not acc_type:
            return {}
        out = {f"TPU-{acc_type}": float(chips)}
        if self.get_worker_id() == 0:
            out[f"TPU-{acc_type}-head"] = 1.0
        return out

    def get_visibility_env(self, ids: List[int]) -> Dict[str, str]:
        return {"TPU_VISIBLE_CHIPS": ",".join(str(i) for i in ids)}

    def validate_resource_request_quantity(self, quantity: float
                                           ) -> Optional[str]:
        if quantity != int(quantity):
            return ("TPU requests must be whole chips "
                    f"(got {quantity}); chips are not fractional")
        if quantity > 0 and quantity not in _VALID_SUBHOST:
            return (f"TPU request of {int(quantity)} chips is not a valid "
                    f"sub-host shape {tuple(int(v) for v in _VALID_SUBHOST)}"
                    "; reserve whole slices via the TPU-<type> pod "
                    "resource instead")
        return None

    # -- mesh construction -------------------------------------------------
    def mesh_shape_hint(self) -> Optional[List[int]]:
        """Parse the physical topology ("2x2x2" → [2, 2, 2]) for
        mesh_utils.create_device_mesh's physical-layout-aware axis
        assignment (parallel/mesh.py consumes this)."""
        topo = self.get_topology()
        if not topo:
            return None
        try:
            dims = [int(x) for x in topo.lower().split("x")]
            return dims if all(d > 0 for d in dims) else None
        except ValueError:
            return None
