"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of the table, so that no change to the program
can move a roofline.  A kind that is not listed is an error, not a
default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,           # dense bf16 FLOP/s
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  'bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s',
    },
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device_kind={device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None
