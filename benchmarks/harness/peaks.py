"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.  An unknown kind is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
1,600 Gbit/s inter-chip interconnect per chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,  # 1,600 Gbit/s
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to benchmarks/harness/peaks.py") from None
