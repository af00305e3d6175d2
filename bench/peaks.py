"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an error:
no roofline share or utilization is computed against a guessed peak.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
