"""Published peaks of each accelerator, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s.  A device kind missing from
this table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, TPU v5e",
        "peak_flops_bf16": 197e12,   # FLOP/s
        "peak_ops_int8": 393e12,     # OP/s
        "hbm_bw": 819e9,             # B/s
        "hbm_bytes": 16 * 1024 ** 3,
    },
}


def peaks_of(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)}); add "
                       "them to bench/peaks.py with their source")
    return PEAKS[device_kind]
