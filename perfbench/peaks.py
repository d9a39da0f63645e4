"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s. A float32 matmul at JAX's
default precision is one bfloat16 pass of the MXU, so its roof is the
bfloat16 peak. A kind missing here is an error, never a default.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e"

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
