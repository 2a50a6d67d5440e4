"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM (Google Cloud
documentation, "TPU v5e").  A device that is not in the table is an
error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_Bps": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
