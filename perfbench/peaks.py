"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a roofline share against a guessed peak is worse than none.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" system
architecture page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip).
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to perfbench/peaks.py (known: {sorted(PEAKS)})"
        ) from None
