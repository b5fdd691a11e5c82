"""The one table of device peaks, keyed by `device_kind` as JAX reports it.
A device that is not here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 394 TOP/s int8,
16 GB HBM2e at 819 GB/s, per chip.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def peak(device_kind, what):
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on file for device kind {device_kind!r} "
                       f"(have {sorted(PEAKS)}): add it with its source")
    return PEAKS[device_kind][what]
