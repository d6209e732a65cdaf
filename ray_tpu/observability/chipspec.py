"""Chip peak-performance table: the denominator of MFU/MBU.

The XLA attribution plane (observability/xla.py) turns a compiled
program's cost analysis into *utilization* only by dividing achieved
FLOP/s and bytes/s by what the chip could do.  This module is the one
place those peaks live:

    spec = lookup("TPU v5 lite")
    mfu  = achieved_flops_per_s / spec.peak_flops

Published peaks (bf16 dense matmul FLOP/s and HBM bandwidth):

    ===========  ==============  =============
    chip         peak FLOP/s     HBM bytes/s
    ===========  ==============  =============
    TPU v4       275e12          1228e9
    TPU v5e      197e12           819e9
    TPU v5p      459e12          2765e9
    ===========  ==============  =============

Rules of the table:

- ``lookup`` normalizes the strings jax reports as ``device_kind``
  ("TPU v5 lite" -> v5e, "TPU v5p" -> v5p, ...).
- CPU backends resolve to a *nominal* spec tagged
  ``measurement="cpu"``: the plumbing (rows, ratios, summaries) works
  identically in tier-1 CPU tests, but every consumer can see the
  ratios prove wiring, not performance.
- A device kind that is not in the table is an **error**
  (`UnknownChipError`), not a default: a guessed denominator turns
  every ratio derived from it into a fabricated number.  Add the row,
  with the source of its peaks.  :data:`UNKNOWN` (``peak_flops is
  None``, MFU/MBU ``None``) stands only for "no single kind to name":
  no device, or a mesh of mixed generations that share no roofline.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class ChipSpec:
    """Peak envelope of one chip generation.

    ``peak_flops``/``peak_hbm_bytes_per_s`` are per-chip bf16 dense
    peaks; ``None`` means the kind is unknown and no utilization ratio
    may be derived from this spec. ``measurement`` tags how rows built
    against this spec should be read: "tpu" (real roofline), "cpu"
    (plumbing proof only), or "unknown".
    """

    spec: str
    peak_flops: Optional[float]
    peak_hbm_bytes_per_s: Optional[float]
    measurement: str = "tpu"

    @property
    def known(self) -> bool:
        return self.peak_flops is not None


# Canonical spec rows, keyed by the normalized generation name.
_SPECS = {
    "v4": ChipSpec("v4", 275e12, 1228e9),
    "v5e": ChipSpec("v5e", 197e12, 819e9),
    "v5p": ChipSpec("v5p", 459e12, 2765e9),
    # Nominal CPU envelope: a modern server core's ~100 GFLOP/s and
    # ~100 GB/s memory stream. The numbers only exist so CPU-tier tests
    # exercise the full MFU/MBU path; the "cpu" tag marks every derived
    # ratio as a plumbing proof, not a performance claim.
    "cpu": ChipSpec("cpu", 100e9, 100e9, measurement="cpu"),
}

UNKNOWN = ChipSpec("unknown", None, None, measurement="unknown")

# device_kind substrings -> canonical generation, checked in order
# (first match wins). A bare "v5" names no generation and matches nothing.
_KIND_PATTERNS = (
    ("v5 lite", "v5e"),
    ("v5litepod", "v5e"),
    ("v5e", "v5e"),
    ("v5p", "v5p"),
    ("v4", "v4"),
    ("cpu", "cpu"),
)


class UnknownChipError(ValueError):
    """A device kind with no row in the peak table."""


def lookup(device_kind: Optional[str]) -> ChipSpec:
    """Resolve a jax ``device_kind`` (or mesh-inventory chip string) to
    its :class:`ChipSpec`. No kind at all (or the literal "unknown" an
    inventory row carries) is :data:`UNKNOWN`; a kind that is not in
    the table raises `UnknownChipError`."""
    if not device_kind:
        return UNKNOWN
    kind = str(device_kind).strip().lower()
    if kind == UNKNOWN.spec:
        return UNKNOWN
    for pattern, gen in _KIND_PATTERNS:
        if pattern in kind:
            return _SPECS[gen]
    raise UnknownChipError(
        f"device kind {device_kind!r} has no row in the chip peak table "
        "(observability/chipspec.py): add one, with the source of its "
        "peaks, rather than guessing a denominator")


def local_spec() -> ChipSpec:
    """Spec of this process's default jax backend (first local device)."""
    import jax

    devices = jax.local_devices()
    if not devices:
        return UNKNOWN
    dev = devices[0]
    if dev.platform == "cpu":
        return _SPECS["cpu"]
    return lookup(getattr(dev, "device_kind", None) or dev.platform)
