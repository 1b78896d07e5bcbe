"""Published peaks of the devices the benchmark knows, keyed by the
``device_kind`` JAX reports. A device that is not in the table is an error,
never a default (copied from ``bench.py`` ``DEVICE_PEAKS``, PR 25).

Source: Google Cloud documentation, "TPU v5e" system architecture page
(per chip: 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            "with its source to benchmark/peaks.py") from None


def roofline_s(flops: float, bytes_moved: float, device_kind: str,
               int8: bool = False) -> tuple:
    """The least time the chip could take for this much work, and which
    bound it is: → (seconds, "compute" | "memory")."""
    p = peaks(device_kind)
    compute = flops / (p["int8_ops_per_s"] if int8 else p["bf16_flops_per_s"])
    memory = bytes_moved / p["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
