"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s). A device that is not in the table is an
error, never a default (the rule `bench.py` `_hbm_peak_gbps` had right).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's peaks "
            f"table ({sorted(PEAKS)}): add it with its source, do not guess"
        ) from None
