"""Published peaks of each chip the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A device that is not in the table is
an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e documentation"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
