"""Published peaks of one chip, keyed by the exact `device_kind` jax
reports. A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30},
}


def for_kind(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a row with its source to "
                       f"benchmark/peaks.py") from None
