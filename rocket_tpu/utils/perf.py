"""Device peak tables — MFU denominators and the roofline cost model's
constants (used by bench.py, the Profiler capsule, and
``analysis/sched_audit.py``)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Union

import jax

__all__ = ["PEAK_FLOPS", "peak_flops", "DeviceSpec", "DEVICE_SPECS",
           "device_spec"]


@dataclass(frozen=True)
class DeviceSpec:
    """Per-device-kind roofline constants.

    ``flops_bf16`` is the bf16 peak (:data:`PEAK_FLOPS` reads it).
    ``hbm_bw`` and ``ici_bw`` are bytes/second — HBM read+write bandwidth and aggregate one-way
    inter-chip bandwidth per chip (all links). ``ici_link_bw`` is ONE
    link's one-way bandwidth (aggregate / link count): a bulk collective
    (XLA's multi-dimensional rings) drives every link at once and is
    priced at the aggregate, but an explicit ``ppermute`` ring hop moves
    its chunk over a single link — the schedule auditor prices those
    hop-by-hop against this column. ``dcn_bw`` is the per-chip
    data-center-network egress bandwidth, the denominator for
    CROSS-SLICE collectives (multi-slice data parallelism — ROADMAP
    item 5); ICI never leaves a slice. ``vmem_bytes`` is a CONSERVATIVE
    per-core scratch budget for pallas kernels, not the hardware
    maximum — a kernel fitting this budget leaves the compiler headroom
    for its own spills. ``hbm_bytes`` is the per-chip HBM CAPACITY (the
    published figure; the serving auditor's RKT603 fit check budgets
    against it). ``ridge`` (FLOPs/byte) is the arithmetic intensity
    above which a kernel is compute-bound.
    """

    kind: str
    flops_bf16: float
    hbm_bw: float
    ici_bw: float
    vmem_bytes: int
    hbm_bytes: int = 16 << 30
    ici_link_bw: float = 0.0
    dcn_bw: float = 25e9

    def __post_init__(self):
        if not self.ici_link_bw:
            # Fallback for ad-hoc specs: a 2D-torus chip has 4 links.
            object.__setattr__(self, "ici_link_bw", self.ici_bw / 4)

    @property
    def ridge(self) -> float:
        return self.flops_bf16 / self.hbm_bw


#: Roofline constants by device kind (longest-prefix matching).
#: Bandwidths are the published per-chip figures; treat
#: them as ranking constants for the static cost model, not measured
#: achievable bandwidth. Link counts: v4/v5p/v7 are 3D tori (6 links),
#: v5e/v6e 2D (4 links); DCN is the per-chip share of the published
#: slice egress — a conservative ranking constant.
DEVICE_SPECS = {
    spec.kind: spec
    for spec in (
        DeviceSpec("TPU v4", 275e12, 1228e9, 300e9, 16 << 20, 32 << 30,
                   ici_link_bw=50e9, dcn_bw=25e9),
        DeviceSpec("TPU v5 lite", 197e12, 819e9, 200e9, 16 << 20,
                   16 << 30, ici_link_bw=50e9, dcn_bw=25e9),         # v5e
        DeviceSpec("TPU v5", 459e12, 2765e9, 600e9, 16 << 20,
                   95 << 30, ici_link_bw=100e9, dcn_bw=50e9),        # v5p
        DeviceSpec("TPU v6 lite", 918e12, 1638e9, 448e9, 32 << 20,
                   32 << 30, ici_link_bw=112e9, dcn_bw=50e9),        # v6e
        DeviceSpec("TPU v6", 918e12, 1638e9, 448e9, 32 << 20, 32 << 30,
                   ici_link_bw=112e9, dcn_bw=50e9),
        DeviceSpec("TPU v7", 2307e12, 7370e9, 1200e9, 32 << 20,
                   192 << 30, ici_link_bw=200e9, dcn_bw=100e9),
    )
}


#: bf16 peak by device kind — MFU denominators, read off the one table
#: above. Matching is longest prefix, so "TPU v5 lite" (v5e) wins over
#: "TPU v5" (v5p) and future suffixed kinds fall back to their family
#: entry.
PEAK_FLOPS = {kind: spec.flops_bf16 for kind, spec in DEVICE_SPECS.items()}


def _longest_prefix(table: dict, kind: str):
    best = None
    for prefix, value in table.items():
        if kind.startswith(prefix) and (best is None or len(prefix) > best[0]):
            best = (len(prefix), value)
    return None if best is None else best[1]


def peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """bf16 peak for the device kind, or None when the kind is not in the
    table. A measurement path (``bench.py``) treats None as an error; the
    live Profiler postfix omits MFU."""
    kind = (device or jax.devices()[0]).device_kind
    # Longest prefix wins ("TPU v5 lite" before "TPU v5").
    return _longest_prefix(PEAK_FLOPS, kind)


def device_spec(
    device: Optional[Union[jax.Device, str]] = None,
) -> Optional[DeviceSpec]:
    """Roofline constants for a device or device-kind string, or None
    when the kind is unknown (callers should skip the roofline rather
    than price against the wrong machine). Accepts the kind directly so
    static auditors can price for hardware that is not present."""
    if isinstance(device, str):
        kind = device
    else:
        kind = (device or jax.devices()[0]).device_kind
    return _longest_prefix(DEVICE_SPECS, kind)
