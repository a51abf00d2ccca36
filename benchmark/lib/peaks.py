"""Published peaks of one chip, keyed by the `device_kind` jax reports.

A device that is not in the table is an error, never a default: a share of
an unknown peak is no number.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": 'Google Cloud documentation, "TPU v5e": '
                              "197 TFLOP/s bf16, 819 GB/s HBM"},
}


def peaks_for(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None
