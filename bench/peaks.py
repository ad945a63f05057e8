"""Published peaks of each accelerator the benchmark runs on, by ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
share or an MFU against a guessed peak is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            f"with its source to bench/peaks.py") from None
