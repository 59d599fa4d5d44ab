"""Published peaks of the devices the benchmark runs on, keyed by
``jax.Device.device_kind``. A device that is not here is an error.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s,
1 600 Gbit/s of inter-chip interconnect per chip.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "ici_bits_per_s": 1.6e12},
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; have "
                       f"{sorted(PEAKS)}")
    return PEAKS[kind]
