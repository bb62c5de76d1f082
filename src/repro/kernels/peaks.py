"""Published per-chip peak rates, keyed by ``jax.Device.device_kind``.

The one table every roofline in the repository reads: the fused-kernel
tile model (:mod:`repro.kernels.autotune`) and the dry-run roofline report
(``benchmarks/roofline.py``).  A device kind that is not listed is an
error, never a default: a tile ranked against the wrong chip's rates is
silently mis-sized.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Peak rates of ONE chip."""

    flops: float              # dense bf16 FLOP/s
    hbm_bytes_per_s: float
    ici_bytes_per_s: float    # chip-to-chip interconnect, all links
    source: str


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bytes_per_s=819e9, ici_bytes_per_s=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip'),
}

#: The deployment chip.  Off-TPU (Pallas interpret mode, described-topology
#: compiles) tiles are ranked against it, so they are the tiles the chip
#: itself would pick.
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises ``ValueError`` for an unlisted kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device_kind={device_kind!r}; add its "
            f"published numbers to repro.kernels.peaks.PEAKS "
            f"(known: {sorted(PEAKS)})") from None
