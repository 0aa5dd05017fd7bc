"""Pod-level roofline: the paper's BSPS cost generalised to three terms.

The paper's hyperstep cost is ``max(T_h, e·ΣC_i)`` — compute vs external-memory
fetch. On a TPU pod a training/serving step has three overlappable resources, so
the per-step cost model becomes

    T_step ≈ max( compute, memory, collective )

with (per the assignment's definitions, global quantities over ``chips``):

    compute    = HLO_FLOPs        / (chips × peak_FLOP/s)
    memory     = HLO_bytes        / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

``compiled.cost_analysis()`` on a GSPMD-partitioned executable reports
*per-device* numbers (the partitioned module), so per-device values × chips give
the globals; the two normalisations cancel and we work per-device directly.

Hardware constants come from :data:`PEAKS`, keyed by the ``device_kind`` JAX
reports; :func:`hardware_spec` refuses a kind that is not in the table.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from repro.core.hlo import CollectiveStats, collective_bytes

__all__ = ["HardwareSpec", "PEAKS", "TPU_V5E", "RooflineReport", "analyze",
           "hardware_spec", "model_flops"]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float          # per chip, FLOP/s (bf16)
    hbm_bandwidth: float       # per chip, bytes/s
    ici_bandwidth: float       # per chip per link, bytes/s
    ici_links: int = 2         # links participating per collective direction
    hbm_bytes: float = 16e9

    @property
    def link_bandwidth(self) -> float:
        return self.ici_bandwidth * self.ici_links


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: TPU v5e (kind "TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip
#: interconnect (taken here as 2 links × 50 GB/s per direction).
PEAKS: dict[str, HardwareSpec] = {
    "TPU v5 lite": HardwareSpec(
        name="tpu-v5e",
        peak_flops=197e12,
        hbm_bandwidth=819e9,
        ici_bandwidth=50e9,
        ici_links=2,
        hbm_bytes=16e9,
    ),
}

TPU_V5E = PEAKS["TPU v5 lite"]


def hardware_spec(device_kind: str) -> HardwareSpec:
    """The peaks of ``device_kind``; an unlisted kind is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class RooflineReport:
    """Three-term roofline for one (arch × shape × mesh) cell."""

    name: str
    chips: int
    # per-device raw quantities from the compiled module
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_stats: CollectiveStats | None
    # model-level useful FLOPs (global): 6·N·D dense / 6·N_active·D MoE
    model_flops_global: float
    hw: HardwareSpec
    # peak memory from compiled.memory_analysis(), bytes per device
    peak_device_bytes: float = 0.0

    # -- the three terms, in seconds ----------------------------------------

    @property
    def compute_seconds(self) -> float:
        return self.hlo_flops / self.hw.peak_flops

    @property
    def memory_seconds(self) -> float:
        return self.hlo_bytes / self.hw.hbm_bandwidth

    @property
    def collective_seconds(self) -> float:
        return self.coll_bytes / self.hw.link_bandwidth

    @property
    def step_seconds(self) -> float:
        """BSPS-style step estimate: max of the three overlapped resources."""
        return max(self.compute_seconds, self.memory_seconds, self.collective_seconds)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_seconds,
            "memory": self.memory_seconds,
            "collective": self.collective_seconds,
        }
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global) — catches remat/redundant compute."""
        total = self.hlo_flops * self.chips
        return self.model_flops_global / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOPs MFU if the step ran exactly at the dominant-term bound."""
        denom = self.step_seconds * self.chips * self.hw.peak_flops
        return self.model_flops_global / denom if denom else 0.0

    def row(self) -> dict[str, Any]:
        return {
            "cell": self.name,
            "chips": self.chips,
            "compute_s": self.compute_seconds,
            "memory_s": self.memory_seconds,
            "collective_s": self.collective_seconds,
            "dominant": self.dominant,
            "model_gflops": self.model_flops_global / 1e9,
            "useful_ratio": self.useful_flops_ratio,
            "roofline_frac": self.roofline_fraction,
            "peak_device_gb": self.peak_device_bytes / 1e9,
        }

    def __str__(self) -> str:
        return (
            f"{self.name}: compute {self.compute_seconds * 1e3:.3f} ms | "
            f"memory {self.memory_seconds * 1e3:.3f} ms | "
            f"collective {self.collective_seconds * 1e3:.3f} ms  "
            f"=> {self.dominant}-bound, useful {self.useful_flops_ratio:.3f}, "
            f"roofline {self.roofline_fraction:.3f}, "
            f"{self.peak_device_bytes / 1e9:.2f} GB/device"
        )


def _cost_dict(compiled: Any) -> dict[str, float]:
    return compiled.cost_analysis()


def _peak_bytes(compiled: Any) -> float:
    try:
        ma = compiled.memory_analysis()
        return float(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        )
    except Exception:
        return 0.0


def analyze(
    name: str,
    lowered: Any,
    compiled: Any,
    *,
    chips: int,
    model_flops_global: float,
    hw: HardwareSpec,
) -> RooflineReport:
    """Build a :class:`RooflineReport` from a jax ``lowered``/``compiled`` pair."""
    cost = _cost_dict(compiled)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    stats = collective_bytes(text)
    return RooflineReport(
        name=name,
        chips=chips,
        hlo_flops=flops,
        hlo_bytes=nbytes,
        coll_bytes=float(stats.total_bytes),
        coll_stats=stats,
        model_flops_global=model_flops_global,
        hw=hw,
        peak_device_bytes=_peak_bytes(compiled),
    )


def model_flops(
    *,
    params: float,
    active_params: float | None,
    tokens: float,
    training: bool,
) -> float:
    """Useful model FLOPs: 6·N·D training / 2·N·D inference (N_active for MoE)."""
    n = active_params if active_params is not None else params
    factor = 6.0 if training else 2.0
    return factor * n * tokens
