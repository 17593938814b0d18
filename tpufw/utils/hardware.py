"""Chip specs and detection — the numbers MFU accounting depends on.

The reference's only hardware contract is an environmental claim ("tested on
4GB+ GPUs", reference ``README.md:7``) and a health gate (``nvidia-smi``,
``README.md:81-84``). The TPU-native equivalent needs real per-chip peak
numbers because MFU — the BASELINE north-star metric (>=35% on v5e-16) — is
tokens/sec * model FLOPs per token / peak FLOPs, and "peak FLOPs" is a
per-generation constant, not something discoverable at runtime.

Public sources for the table: Google Cloud TPU system architecture docs.
"""

from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Static description of one accelerator chip generation."""

    name: str
    # Peak dense matmul throughput in FLOP/s at the listed dtype.
    peak_bf16_flops: float
    hbm_bytes: int
    # ICI links per chip — used by the mesh layer to sanity-check topologies.
    ici_links: int = 4
    # Peak HBM bandwidth in bytes/s — the roofline's second axis
    # (tpufw.obs.roofline). 0 = unknown; consumers must degrade.
    hbm_bw_bytes_per_s: float = 0.0
    # Largest host (VM) chip count offered for the generation — the
    # upper bound on a pod's google.com/tpu limit, cross-checked by
    # tpulint TPU010 against the deploy manifests. v5e/v6e offer 1/4/8
    # chip hosts; v4/v5p hosts are fixed at 4.
    chips_per_host: int = 4

    @property
    def hbm_gib(self) -> float:
        return self.hbm_bytes / 2**30


# Peak bf16 FLOP/s per chip. v5e: 197 TFLOP/s bf16, 16 GiB HBM at
# 819 GB/s. v5p: 459 TFLOP/s bf16, 95 GiB at 2765 GB/s. v4: 275
# TFLOP/s, 32 GiB at 1228 GB/s. v6e (Trillium): 918 TFLOP/s bf16,
# 32 GiB at 1640 GB/s.
CHIP_SPECS: dict[str, ChipSpec] = {
    "v4": ChipSpec("v4", 275e12, 32 * 2**30, hbm_bw_bytes_per_s=1.228e12),
    "v5e": ChipSpec(
        "v5e", 197e12, 16 * 2**30,
        hbm_bw_bytes_per_s=8.19e11, chips_per_host=8,
    ),
    "v5p": ChipSpec("v5p", 459e12, 95 * 2**30, hbm_bw_bytes_per_s=2.765e12),
    "v6e": ChipSpec(
        "v6e", 918e12, 32 * 2**30,
        hbm_bw_bytes_per_s=1.64e12, chips_per_host=8,
    ),
}

# ``device.device_kind`` patterns. A v5e chip reports "TPU v5 lite"
# (what the chip tool's machine printed, PR 21).
_KIND_PATTERNS: list[tuple[str, str]] = [
    (r"v6e|v6 ?lite|trillium", "v6e"),
    (r"v5p", "v5p"),
    (r"v5 ?lite|v5e|v5litepod", "v5e"),
    (r"v4", "v4"),
]


def detect_chip(device=None) -> ChipSpec | None:
    """Map a jax device (default: ``jax.devices()[0]``) to its ChipSpec.

    Works off ``device.device_kind`` strings like "TPU v5 lite" / "TPU v5e".
    A CPU device has no row — None, so MFU and roofline figures are absent
    from a CPU run instead of invented. An accelerator the table does not
    know is an error: a peak borrowed from another chip makes every
    utilization figure wrong without saying so.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for pattern, name in _KIND_PATTERNS:
        if re.search(pattern, kind):
            return CHIP_SPECS[name]
    raise ValueError(
        f"unknown accelerator device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}): add its peaks to "
        "tpufw.utils.hardware.CHIP_SPECS"
    )
