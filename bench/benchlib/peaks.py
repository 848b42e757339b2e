"""Published peaks of each accelerator the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and 819 GB/s
    # of HBM bandwidth per chip. JAX names the chip "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}") from None
