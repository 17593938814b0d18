"""Roofline peaks and classification — the static half of the perf
observatory (tpufw.obs.perf).

A compiled program's arithmetic intensity AI = FLOPs / bytes-accessed
puts it on one side of the machine balance point
``peak FLOP/s / peak HBM bytes/s``: below it the program cannot reach
peak FLOPs no matter how good the schedule (memory-bound), above it
the HBM is not the wall (compute-bound). The peaks come from the
per-generation chip table (tpufw.utils.hardware) with env overrides
— ``TPUFW_PEAK_FLOPS`` / ``TPUFW_PEAK_HBM_BW`` — for what-if
analysis against a different roofline (docs/PERF.md).

Kept jax-free: the one jax call (device-kind detection) is behind
``detect_peaks(device=...)``'s default and callers (tests,
scripts/obs_summary.py) can pass an explicit spec instead.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from tpufw.utils.hardware import ChipSpec, detect_chip
from tpufw.workloads.env import env_float


@dataclasses.dataclass(frozen=True)
class PeakSpec:
    """The two roofline ceilings plus the HBM capacity headroom math
    needs, resolved for one chip generation (or overridden)."""

    chip: str
    flops_per_s: float
    hbm_bw_bytes_per_s: float
    hbm_bytes: int

    @property
    def balance_flops_per_byte(self) -> float:
        """Machine balance point: the AI at which compute and memory
        time are equal. 0 when bandwidth is unknown."""
        if self.hbm_bw_bytes_per_s <= 0:
            return 0.0
        return self.flops_per_s / self.hbm_bw_bytes_per_s


def peaks_from_spec(spec: ChipSpec) -> PeakSpec:
    """ChipSpec -> PeakSpec with the TPUFW_PEAK_* env overrides
    applied (0/unset keeps the table value)."""
    flops = env_float("peak_flops", 0.0) or spec.peak_bf16_flops
    bw = env_float("peak_hbm_bw", 0.0) or spec.hbm_bw_bytes_per_s
    return PeakSpec(
        chip=spec.name,
        flops_per_s=float(flops),
        hbm_bw_bytes_per_s=float(bw),
        hbm_bytes=spec.hbm_bytes,
    )


def detect_peaks(device=None) -> PeakSpec:
    """Peaks for the running backend's chip (default device). A CPU
    device has no table row: every peak is 0 = unknown, and consumers
    leave MFU, bound and headroom out instead of inventing them. An
    accelerator the table does not know raises (tpufw.utils.hardware)."""
    spec = detect_chip(device)
    if spec is None:
        return PeakSpec(
            chip="cpu", flops_per_s=0.0, hbm_bw_bytes_per_s=0.0, hbm_bytes=0
        )
    return peaks_from_spec(spec)


def classify(
    ai_flops_per_byte: Optional[float], peaks: PeakSpec
) -> Optional[str]:
    """"compute" / "memory" against the machine balance point; None
    when either side of the comparison is unknown (no bytes-accessed
    figure from XLA, or no bandwidth for this chip)."""
    if ai_flops_per_byte is None or ai_flops_per_byte <= 0:
        return None
    balance = peaks.balance_flops_per_byte
    if balance <= 0:
        return None
    return "compute" if ai_flops_per_byte >= balance else "memory"


def attainable_flops_per_s(
    ai_flops_per_byte: float, peaks: PeakSpec
) -> float:
    """The roofline itself: min(peak FLOPs, AI * peak bandwidth) —
    the ceiling a program with this AI can reach on this chip."""
    if peaks.hbm_bw_bytes_per_s <= 0:
        return peaks.flops_per_s
    return min(
        peaks.flops_per_s,
        max(0.0, ai_flops_per_byte) * peaks.hbm_bw_bytes_per_s,
    )
