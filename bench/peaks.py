"""Peak rates of the chips the benchmark runs on, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip.
A device kind that is not in this table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
