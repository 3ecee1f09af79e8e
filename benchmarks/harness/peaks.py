"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error, never
a default: a share of an unknown peak means nothing."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            "to benchmarks/harness/peaks.py with its source"
        ) from None
