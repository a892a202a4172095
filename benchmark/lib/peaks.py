"""Published peaks of the chips the benchmark knows, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(one chip: 197 TFLOP/s in bfloat16, 16 GB of HBM2e at 819 GB/s); the
same figures the ``on-chip-measurement`` guide gives, and the v5e rows
of the repo's ``benchlib.PEAK_BF16_FLOPS`` / ``PEAK_HBM_BYTES_PER_SEC``.
A device that is not in the table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published {what} for device kind {device_kind!r} in "
            "benchmark/lib/peaks.py") from None
