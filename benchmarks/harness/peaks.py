"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

A share of a roofline or of a peak is always taken of these numbers, never
of a probe. A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s, per chip.
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmarks/harness/peaks.py with its "
                       f"source") from None
