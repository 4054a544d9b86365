"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports,
and the least time they allow for a count of operations and bytes.

A device that is not in the table is an error, never a default: a share
of a peak that belongs to another chip means nothing.
"""

from __future__ import annotations

#: bytes an element takes at a configuration's stated compute dtype
BYTES_AT = {"bfloat16": 2, "float16": 2, "float32": 4}

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


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peaks["bf16_flops"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
