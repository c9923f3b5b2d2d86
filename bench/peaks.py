"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default. No VPU
(vector unit) peak is listed: none is published with a source, so the l1
kernel's roofline is bounded by memory bandwidth alone.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def least_seconds(device_kind: str, *, flops: float = 0.0,
                  bytes_: float = 0.0) -> float:
    """The least time the chip could take: the larger of the operations at
    the bf16 matrix peak and the bytes at the memory bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops_per_s"], bytes_ / p["hbm_bytes_per_s"])


def share_pct(least_s: float, kernel_s: float):
    """A kernel's share of its roofline, in %; None where the trace holds
    no time for the kernel (nothing to read)."""
    if kernel_s <= 0.0 or least_s <= 0.0:
        return None
    return 100.0 * least_s / kernel_s
