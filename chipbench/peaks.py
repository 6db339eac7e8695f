"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s in bfloat16 and 819 GB/s of HBM
bandwidth (Google Cloud documentation, "TPU v5e"). The cascade's float32
matmuls and convolutions run at XLA's default TPU precision, one bfloat16
pass with float32 accumulation, so the bfloat16 peak bounds them.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known {sorted(PEAKS)}") from None
