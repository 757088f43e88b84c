"""Peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s per chip. (Copied from
``p2p_tpu/obs/costmodel.py:PLATFORM_PEAKS``, which stays the program's.)
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       "benchmarks/lib/peaks.py with its source")
    return PEAKS[device_kind]
