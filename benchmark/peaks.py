"""Peaks of one chip, by ``device_kind``, with their source.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect), as quoted in the on-chip-measurement guide. A
device that is not in the table is an error, not a default (copied rule
from ``bench.py:_peak_flops``; that original is listed in PERF.md for a
later PR to delete).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
    "TPU v5e": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
