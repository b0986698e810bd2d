"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/
docs/v5e): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.  A kind
that is not here is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
