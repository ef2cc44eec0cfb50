"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def lookup(device_kind: str) -> dict:
    """The peaks of ``device_kind``. A device not in the table is an
    error: no number is measured against a peak that was guessed."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add the chip's published peaks to "
            "bench/peaks.py with their source.")
    return PEAKS[device_kind]
