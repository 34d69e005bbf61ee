"""Published peaks of the chips this benchmark may run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (one chip: 197 TFLOP/s in
bf16, 16 GB of HBM at 819 GB/s). A device that is not in the table is an
error, never a default, and no environment variable overrides a row.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"chipbench.peaks: no published peak for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
