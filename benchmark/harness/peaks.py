"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip. A device
that is not in the table is an error, not a default.
"""

from __future__ import annotations

_V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}    # JAX's two names for it


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"'{device_kind}' (table has {sorted(PEAKS)})")
    return PEAKS[device_kind]


def roofline_share_pct(work: dict, device_kind: str, seconds: float) -> float:
    """The least time the chip could take for ``work`` (``bytes``, ``flops``
    per call: the larger of the two bounds) over the ``seconds`` the call
    took on the device, in percent."""
    pk = peaks_for(device_kind)
    least = max(work["bytes"] / pk["bytes_per_s"],
                work["flops"] / pk["flops_per_s"])
    return least / seconds * 100.0
