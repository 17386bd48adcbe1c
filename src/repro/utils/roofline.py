"""Roofline-term calculator and the per-chip peak table.

Three terms per (arch x mesh), each an estimated lower-bound execution time
in seconds (system-prompt recipe):

  compute    = HLO_FLOPs        / (chips * peak_flops)
  memory     = HLO_bytes        / (chips * hbm_bw)
  collective = collective_bytes / (chips * link_bw)

cost_analysis() reports whole-program numbers for one logical program; on a
mesh the program is SPMD so flops/bytes are already per-partition when XLA
compiles with SPMD partitioning — we therefore DO NOT divide by chips again
for those, only for quantities that are genuinely global. To keep this
unambiguous the caller says whether the numbers are per-device already.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str
    peak_flops: float   # bf16 FLOP/s
    hbm_bw: float       # bytes/s
    link_bw: float      # bytes/s per ICI link


# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# TPU v5e (device_kind "TPU v5 lite"): Google Cloud documentation, "TPU
# v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interchip
# interconnect, i.e. 50 GB/s on each of its four ICI links.
TPUv5e = Chip(name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)

PEAKS = {"TPU v5 lite": TPUv5e}


def chip_for(device_kind: str) -> Chip:
    """The peak table entry for ``device_kind``; an unknown kind raises —
    a roofline against some other chip's peaks is not a measurement."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip to utils/roofline.PEAKS with its "
            f"source)") from None


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    bytes_hbm: float
    bytes_collective: float
    chips: int
    chip: Chip
    model_flops: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Upper bound on achievable MFU at the roofline: the fraction of
        peak the dominant term permits for the *useful* flops."""
        denom = self.bound_s * self.chips * self.chip.peak_flops
        return self.model_flops / denom if denom else 0.0

    def row(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_ratio": self.useful_ratio,
            "mfu_bound": self.mfu_bound,
        }


def roofline_terms(
    *,
    flops_per_device: float,
    hbm_bytes_per_device: float,
    collective_bytes_per_device: float,
    chips: int,
    chip: Chip,
    model_flops: float = 0.0,
) -> RooflineTerms:
    """All inputs are per-device (SPMD-partitioned) quantities; ``chip``
    names the peaks they are held to (:func:`chip_for`)."""
    return RooflineTerms(
        compute_s=flops_per_device / chip.peak_flops,
        memory_s=hbm_bytes_per_device / chip.hbm_bw,
        collective_s=collective_bytes_per_device / chip.link_bw,
        flops=flops_per_device,
        bytes_hbm=hbm_bytes_per_device,
        bytes_collective=collective_bytes_per_device,
        chips=chips,
        model_flops=model_flops,
        chip=chip,
    )


def model_flops(
    *,
    n_params_active: float,
    tokens: float,
    training: bool,
) -> float:
    """MODEL_FLOPS = 6*N*D for training, 2*N*D for inference (per step)."""
    per_token = 6.0 if training else 2.0
    return per_token * n_params_active * tokens
