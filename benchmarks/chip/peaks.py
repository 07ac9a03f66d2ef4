"""Published peaks of each accelerator the benchmark runs on, keyed by
JAX's ``device_kind``.

A device that is not in the table is an error, not a default: a share
of a peak is only as good as the peak it is taken against.
"""

PEAKS = {
    "TPU v5 lite": {
        # Google Cloud documentation, "TPU v5e" (system architecture,
        # per-chip specifications)
        "source": "Google Cloud documentation, TPU v5e, per-chip specs",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


class UnknownDeviceError(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; raises for a device not in the
    table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
