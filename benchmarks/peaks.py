"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error, never
a default: no share of a peak is scored against a guess.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9, "source": "cloud.google.com/tpu/docs/v5e"}
# JAX reports a v5e chip as "TPU v5 lite"
PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in benchmarks/peaks.py; add "
            f"its published peaks with their source") from None
