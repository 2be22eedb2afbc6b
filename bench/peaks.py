"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A kind that is not in the table is an error, never a default.
"""

_V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; ValueError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
