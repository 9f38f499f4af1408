"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default."""
from __future__ import annotations

SOURCE = ("Google Cloud documentation, 'TPU v5e' "
          "(https://cloud.google.com/tpu/docs/v5e)")

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)} ({SOURCE})"
        ) from None
