"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
HBM at 819 GB/s, per chip. Copied from ``tools/kernel_bench.py`` ``CHIP_PEAKS``
so that no PR that claims a gain can change the denominators. A float32
matmul at the TPU's default precision is one bf16 pass, so the bf16 peak is
the ceiling of the float32 serving model too. A device that is not in the
table is an error, never a default.
"""

CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def chip_peak(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no published peak for device_kind={device_kind!r}; "
            f"add it to benchmark/harness/peaks.py with its source") from None


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """The least time the chip could take for that work, and which of the
    two peaks bounds it ("flops" or "bytes")."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
