"""Per-device gauges: HBM in use / capacity and device inventory.

Sampled by the metrics flusher (registered lazily as a flush sampler —
``ray_tpu.util.metrics.register_flush_sampler``), so any process that
touches the observability plane exports its accelerator view on the
same cadence as its other metrics. Idle-HBM headroom and a device
count that doesn't match the slice topology are the first things to
check when a TPU job underperforms.

Deliberately conservative about initialization: sampling NEVER
initializes a jax backend — a process that opens the TPU backend holds
the chip until it exits, and most processes that flush metrics (driver,
daemons, CPU workers) must stay off it — it only reads from backends
that are already live.
"""

from __future__ import annotations

import sys
from typing import Dict

_gauges = None
_registered = False


def _device_gauges():
    global _gauges
    if _gauges is None:
        from ray_tpu.util.metrics import Gauge

        _gauges = {
            "used": Gauge(
                "device_hbm_used_bytes",
                description="Device memory in use (device.memory_stats "
                            "bytes_in_use).",
                tag_keys=("device", "kind")),
            "total": Gauge(
                "device_hbm_total_bytes",
                description="Device memory capacity (device.memory_stats "
                            "bytes_limit).",
                tag_keys=("device", "kind")),
            "count": Gauge(
                "device_count",
                description="Visible devices by kind/platform.",
                tag_keys=("kind", "platform")),
        }
    return _gauges


def _live_backend_devices():
    """Devices of already-initialized backends only; [] otherwise."""
    if "jax" not in sys.modules:
        return []
    try:
        from jax._src import xla_bridge

        if not getattr(xla_bridge, "_backends", None):
            return []
        import jax

        return list(jax.devices())
    except Exception:
        return []


def sample_device_metrics() -> int:
    """Set the device gauges from the live backend; returns the number
    of devices sampled (0 when no backend is initialized)."""
    devices = _live_backend_devices()
    if not devices:
        return 0
    g = _device_gauges()
    by_kind: Dict[tuple, int] = {}
    for d in devices:
        kind = getattr(d, "device_kind", "unknown")
        platform = getattr(d, "platform", "unknown")
        by_kind[(kind, platform)] = by_kind.get((kind, platform), 0) + 1
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        tags = {"device": str(getattr(d, "id", "?")), "kind": kind}
        used = ms.get("bytes_in_use")
        total = ms.get("bytes_limit") or ms.get("bytes_reservable_limit")
        if used is not None:
            g["used"].set(float(used), tags=tags)
        if total is not None:
            g["total"].set(float(total), tags=tags)
    for (kind, platform), n in by_kind.items():
        g["count"].set(float(n), tags={"kind": kind,
                                       "platform": platform})
    return len(devices)


def ensure_sampler_registered() -> None:
    """Idempotently hook device sampling into the metrics flusher."""
    global _registered
    if _registered:
        return
    _registered = True
    from ray_tpu.util.metrics import register_flush_sampler

    register_flush_sampler(sample_device_metrics)
