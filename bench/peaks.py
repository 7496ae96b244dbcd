"""Peak rates of each chip the benchmark runs on, keyed by JAX's
``device_kind``.  A chip that is not in the table is an error."""

SOURCE = "Google Cloud documentation, 'TPU v5e' (cloud.google.com/tpu/docs/v5e)"

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
