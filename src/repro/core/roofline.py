"""Three-term roofline model per chip kind (the §Roofline deliverable).

    compute term    = HLO_FLOPs_per_device   / peak_FLOP/s
    memory term     = HLO_bytes_per_device   / HBM_bw
    collective term = wire_bytes_per_device  / link_bw

IMPORTANT calibration note (verified empirically on this jax/XLA build):
``compiled.cost_analysis()`` on an SPMD-partitioned module reports the
numbers of the *per-device* program (the module each chip executes), NOT
global totals.  The same holds for ``memory_analysis()``.  So the terms
below take per-device numerators and per-chip denominators; ``chips`` is
only used to convert the (global) MODEL_FLOPS into per-device useful work
for MFU.
"""

from __future__ import annotations

import dataclasses
from typing import Dict



@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # B/s
    hbm_bytes: int
    ici_bw_per_link: float  # B/s


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect per chip (four links).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 1000**3,
        ici_bw_per_link=50e9,
    ),
}

# the chip the dry-run models on the CPU (``jax.devices()`` there is not it)
V5E = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """The three terms (seconds per step) and their inputs.

    ``hlo_flops`` / ``hlo_bytes`` / ``collective_bytes`` are PER-DEVICE
    (what one chip executes/moves); ``model_flops`` is GLOBAL useful work.
    """

    name: str
    device_kind: str  # a key of PEAKS
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float  # wire bytes per device
    model_flops: float = 0.0  # 6*N*D useful-work model (global)

    @property
    def peaks(self) -> ChipPeaks:
        return peaks_for(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw_per_link

    @property
    def bound(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def step_s(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_fraction(self) -> float:
        """MODEL_FLOPS / (chips * HLO_FLOPs): useful share of compiled compute."""
        total_hlo = self.hlo_flops * self.chips
        if total_hlo <= 0:
            return 0.0
        return self.model_flops / total_hlo

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        if self.step_s <= 0:
            return 0.0
        return self.model_flops / (self.step_s * self.chips * self.peaks.flops_bf16)

    @property
    def roofline_fraction(self) -> float:
        """Dominant-term efficiency: compute_s / step_s (1.0 = compute-bound
        at peak; the score we hillclimb)."""
        if self.step_s <= 0:
            return 0.0
        return self.compute_s / self.step_s

    def as_dict(self) -> Dict[str, float]:
        return {
            "chips": self.chips,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "step_s": self.step_s,
            "mfu": self.mfu,
            "useful_flop_fraction": self.useful_flop_fraction,
            "roofline_fraction": self.roofline_fraction,
        }

    def summary(self) -> str:
        return (
            f"{self.name}: compute {self.compute_s*1e3:.2f}ms | "
            f"memory {self.memory_s*1e3:.2f}ms | "
            f"collective {self.collective_s*1e3:.2f}ms -> {self.bound}-bound; "
            f"useful-FLOP {100*self.useful_flop_fraction:.0f}%, "
            f"MFU@roofline {100*self.mfu:.1f}%"
        )
