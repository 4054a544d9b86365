"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, never a default: a share
of a peak that belongs to another chip means nothing.
"""

from __future__ import annotations

#: source: Google Cloud documentation, "TPU v5e" system architecture
#: (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip)
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add a row with its source, do not guess."
        ) from None
