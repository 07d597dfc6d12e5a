"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
