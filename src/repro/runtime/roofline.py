"""Chip peak rates, and the three-term roofline model priced with them.

    compute    = HLO_FLOPs        / (chips × peak FLOP/s)
    memory     = HLO_bytes        / (chips × peak HBM bytes/s)
    collective = collective_bytes / (chips × ICI bytes/s per link)

``CHIP_PEAKS`` is the repository's one table of chip peaks, keyed by
``jax.Device.device_kind``; the cost model (``core/cost_model.py``), the
kernel block chooser (``kernels/ops.py``) and this roofline all read it.
A TPU whose kind is not in the table is an error, never a default.

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (whole-module,
all chips → divide by chip count); collective_bytes comes from
``runtime.hlo.parse_collectives`` over the post-partitioning module text
(per-chip traffic already).  MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D
(MoE) gives the useful-compute ratio.
"""
from __future__ import annotations

import dataclasses
import functools

import jax


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Peak rates and compiler limits of one chip."""

    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # ICI bytes/s per link
    vpu_ops: float      # elementwise vector ops/s
    vmem_limit: int     # Mosaic's default scoped-VMEM bytes per kernel


#: Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU
#: v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect
#: over four links (50 GB/s each).  ``vpu_ops`` is not published: it is
#: the cost model's uncalibrated guess.  ``vmem_limit`` is the limit the
#: TPU compiler enforces on one Pallas kernel by default ("limit 16.00M"
#: in its out-of-VMEM error).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                             vpu_ops=4.0e12, vmem_limit=16 * 2 ** 20),
}

#: The chip the kernels are sized and ranked for in a process without a
#: TPU: CPU tests, interpret mode and compile rehearsals against a
#: described v5e topology.
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}: add it to "
            "runtime/roofline.CHIP_PEAKS with its published source") from None


@functools.lru_cache(maxsize=None)
def local_peaks() -> ChipPeaks:
    """Peaks of this process's TPU, or of :data:`TARGET_KIND` where the
    process has no TPU."""
    dev = jax.devices()[0]
    return chip_peaks(dev.device_kind if dev.platform == "tpu"
                      else TARGET_KIND)


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    chips: int
    model_flops: float = 0.0
    peak_flops: float = 0.0     # per chip, from CHIP_PEAKS

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful-FLOPs time / bound time — the score we hillclimb."""
        if self.bound_s <= 0:
            return 0.0
        return (self.model_flops / (self.chips * self.peak_flops)) \
            / self.bound_s

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "chips": self.chips,
        }


def terms_from_analysis(cost: dict, collective_bytes: float,
                        chips: int, model_flops: float = 0.0,
                        peaks: ChipPeaks | None = None) -> RooflineTerms:
    """``cost`` is ``compiled.cost_analysis()`` of the PER-DEVICE SPMD
    module (XLA reports per-device flops/bytes — verified empirically), and
    ``collective_bytes`` is the per-device link traffic.  Multiplying back
    by ``chips`` recovers the spec's global-HLO formulation:
    global_flops / (chips × peak) == per_device_flops / peak.  ``peaks``
    defaults to :func:`local_peaks`."""
    peaks = peaks or local_peaks()
    flops = float(cost.get("flops", 0.0))
    b = float(cost.get("bytes accessed", 0.0))
    return RooflineTerms(
        compute_s=flops / peaks.flops,
        memory_s=b / peaks.hbm_bw,
        collective_s=collective_bytes / peaks.ici_bw,
        hlo_flops=flops * chips,           # global, for the useful ratio
        hlo_bytes=b * chips,
        collective_bytes=collective_bytes, chips=chips,
        model_flops=model_flops, peak_flops=peaks.flops)


def model_flops_train(cfg, n_tokens: int) -> float:
    """6·N·D (dense) or 6·N_active·D (MoE) for one training step."""
    return 6.0 * cfg.active_param_count() * n_tokens


def model_flops_decode(cfg, n_tokens: int) -> float:
    """2·N_active per generated token (forward only)."""
    return 2.0 * cfg.active_param_count() * n_tokens


def model_flops_prefill(cfg, n_tokens: int) -> float:
    return 2.0 * cfg.active_param_count() * n_tokens
