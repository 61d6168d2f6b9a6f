"""The one table of chip peaks, keyed by JAX's exact `device_kind`.

A device that is not here is an error, never a default: a share of a peak
priced at another chip's peak is a wrong number under a right name.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}; "
                       f"add it to chipbench/peaks.py with its source") \
            from None


def least_seconds(flops, bytes_moved, peak):
    """The least time the chip could take for `flops` operations and
    `bytes_moved` bytes: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_moved / peak["hbm_bytes_per_s"])
