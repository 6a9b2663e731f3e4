"""Peaks of the chips the benchmark may run on, keyed by ``device_kind``.

One table, with its source.  A device that is not in it is an error, never
a default: without a peak there is no roofline share and no MFU.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak on record for device kind {device_kind!r}: "
                       "add it to benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]
