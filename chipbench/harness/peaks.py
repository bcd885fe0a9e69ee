"""Published per-chip peaks, keyed by a substring of JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip). A device that is not
in the table is an error, not a default: a share of some other chip's peak
is not a measurement.
"""

from __future__ import annotations

PEAKS = {
    "v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


class UnknownDeviceKind(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    kind = device_kind.lower()
    for sub, row in PEAKS.items():
        if sub in kind:
            return row
    raise UnknownDeviceKind(
        f"device_kind {device_kind!r} is not in chipbench/harness/peaks.py")
