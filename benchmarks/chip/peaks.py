"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. One table; a device that is not in it is an
error, never a default."""

from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (per chip): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "source": "cloud.google.com/tpu/docs/v5e (per chip)",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmarks/chip/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their source"
        ) from None
