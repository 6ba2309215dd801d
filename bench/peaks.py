"""Published peaks of each chip the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, not a
default: a roofline share against the wrong peak is a wrong number."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
SOURCE = {"TPU v5 lite": 'Google Cloud documentation, "TPU v5e"'}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]
