"""TPU accelerator manager — first-class TPU detection and scheduling glue.

Reference: `python/ray/_private/accelerators/tpu.py` (`TPUAcceleratorManager`
at `:75`; chip autodetect via `/dev/accel*`/vfio + GCE metadata at `:52`;
`TPU_VISIBLE_CHIPS` + host-bounds env setting at `:158`; pod-aware extra
resources `TPU-{type}-head` and per-pod-name resource at `:335`; request
quantity enforcement at `:144`).

Detection priority:
1. ``RAY_TPU_FAKE_CHIPS`` env (tests: fake N chips without hardware),
2. ``/dev/accel*`` device files (PCI TPU VM),
3. ``/dev/vfio`` entries (newer TPU VM images),
4. GCE metadata server (pod topology / accelerator type).

Detection never touches JAX: asking JAX for devices opens the TPU backend,
and the process that does so holds the chip until it exits — the node
daemons and the driver must stay off it so a leased worker can own it.  A
process that owns its chips (a leased worker, a single-process launcher)
counts them with ``jax.devices()`` itself.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from ray_tpu.accelerators.accelerator import AcceleratorManager

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
FAKE_CHIPS_ENV = "RAY_TPU_FAKE_CHIPS"
FAKE_POD_TYPE_ENV = "RAY_TPU_FAKE_POD_TYPE"  # e.g. "v5e-16"
FAKE_POD_NAME_ENV = "RAY_TPU_FAKE_POD_NAME"
FAKE_WORKER_ID_ENV = "RAY_TPU_FAKE_WORKER_ID"

GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1"

# Valid single-host chip request sizes (reference tpu.py:144: {1, 2, 4}).
VALID_CHIP_COUNTS = (1, 2, 4)


def _gce_metadata(path: str) -> Optional[str]:
    try:
        import urllib.request

        req = urllib.request.Request(
            f"{GCE_METADATA_URL}/{path}",
            headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=1) as resp:
            return resp.read().decode()
    except Exception:
        return None


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        fake = os.environ.get(FAKE_CHIPS_ENV)
        if fake is not None:
            return int(fake)
        chips = glob.glob("/dev/accel*")
        if chips:
            return len(chips)
        vfio = glob.glob("/dev/vfio/[0-9]*")
        if vfio:
            return len(vfio)
        return 0

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        fake = os.environ.get(FAKE_POD_TYPE_ENV)
        if fake:
            return fake
        accel_type = _gce_metadata("instance/attributes/accelerator-type")
        return accel_type

    @staticmethod
    def get_current_pod_name() -> Optional[str]:
        fake = os.environ.get(FAKE_POD_NAME_ENV)
        if fake:
            return fake
        return _gce_metadata("instance/attributes/instance-id")

    @staticmethod
    def get_current_pod_worker_count() -> Optional[int]:
        accel_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        if accel_type is None:
            return None
        chips = _pod_chip_count(accel_type)
        if chips is None:
            return None
        per_host = TPUAcceleratorManager.get_current_node_num_accelerators() or 4
        return max(1, chips // per_host)

    @staticmethod
    def validate_resource_request_quantity(quantity: float
                                           ) -> Tuple[bool, Optional[str]]:
        if quantity != int(quantity):
            if 0 < quantity < 1:
                return True, None  # fractional share of one chip
            return False, f"TPU request must be integral or <1, got {quantity}"
        if int(quantity) in VALID_CHIP_COUNTS or quantity == 0:
            return True, None
        return (False,
                f"TPU request quantity must be one of {VALID_CHIP_COUNTS} "
                f"(a single host's chips cannot be split further); got "
                f"{quantity}. For multi-host slices use pod gang resources "
                f"(e.g. 'TPU-v5e-16-head').")

    @staticmethod
    def set_current_process_visible_accelerator_ids(ids: List[str]) -> None:
        """Only effective before this process initialises a JAX backend;
        the raylet applies the same variables to a leased worker's spawn
        environment (`apply_visible_chips`) for exactly that reason."""
        apply_visible_chips(os.environ, ids)

    @staticmethod
    def get_current_node_extra_resources() -> Dict[str, float]:
        """Pod-gang resources (reference tpu.py:335): every host in a slice
        carries `TPU-{type}` and the pod-name resource; worker 0 additionally
        carries `TPU-{type}-head` so exactly one task can claim the slice."""
        out: Dict[str, float] = {}
        accel_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        if not accel_type:
            return out
        version = _accel_version(accel_type)
        if version:
            out[f"TPU-{version}"] = \
                TPUAcceleratorManager.get_current_node_num_accelerators() or 1
        pod_name = TPUAcceleratorManager.get_current_pod_name()
        if pod_name:
            out[f"{pod_name}"] = 1
        worker_id = os.environ.get(FAKE_WORKER_ID_ENV)
        if worker_id is None:
            worker_id = _gce_metadata("instance/attributes/agent-worker-number")
        if worker_id is not None and str(worker_id).strip() == "0":
            out[f"TPU-{accel_type}-head"] = 1
        return out


def apply_visible_chips(env, ids) -> None:
    """Make the process that runs under `env` (a spawn environment, or
    ``os.environ`` before JAX initialises) see exactly chips `ids`.
    Single- and two-chip processes must also shrink the host bounds so the
    TPU runtime doesn't try to grab the full host (reference tpu.py:158)."""
    env[TPU_VISIBLE_CHIPS_ENV] = ",".join(str(i) for i in ids)
    n = len(ids)
    if n == 1:
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = "1,1,1"
        env[TPU_HOST_BOUNDS_ENV] = "1,1,1"
    elif n == 2:
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = "1,2,1"
        env[TPU_HOST_BOUNDS_ENV] = "1,1,1"
    else:
        env.pop(TPU_CHIPS_PER_HOST_BOUNDS_ENV, None)
        env.pop(TPU_HOST_BOUNDS_ENV, None)


def _accel_version(accel_type: str) -> Optional[str]:
    """'v5litepod-16' -> 'v5litepod'; 'v5e-16' -> 'v5e'; 'v4-8' -> 'v4'."""
    m = re.match(r"^(v\d+[a-z]*)-(\d+)$", accel_type)
    return m.group(1) if m else None


def _pod_chip_count(accel_type: str) -> Optional[int]:
    m = re.match(r"^v\d+[a-z]*-(\d+)$", accel_type)
    if not m:
        return None
    n = int(m.group(1))
    # v2/v3/v4 advertise cores; v5e/v5p/v6e advertise chips. Treat the suffix
    # as the chip count for v5e-style names.
    return n


# ---------------------------------------------------------------------------
# Public helpers (reference: `python/ray/util/accelerators/tpu.py`).
# ---------------------------------------------------------------------------

def pod_head_resource(accel_type: str) -> Dict[str, float]:
    """Resource demand that gang-claims a whole pod slice via its head."""
    return {f"TPU-{accel_type}-head": 1}


def get_current_pod_worker_count() -> Optional[int]:
    return TPUAcceleratorManager.get_current_pod_worker_count()


def get_current_pod_name() -> Optional[str]:
    return TPUAcceleratorManager.get_current_pod_name()


def get_num_tpu_chips_on_node() -> int:
    return TPUAcceleratorManager.get_current_node_num_accelerators()

