"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip
interconnect.  A device kind that is not here is an error, never a
default.  No metric of the benchmark reads it yet: it is kept for the
roofline shares that a later PR adds.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
