"""Published peaks of one chip, keyed by jax's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, HBM2e at 819 GB/s. Only what a reader uses is listed. A
device that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; the table has "
            f"{sorted(PEAKS)}. Add the device with its source."
        ) from None
