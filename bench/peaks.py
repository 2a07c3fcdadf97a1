"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

An unknown device is an error, not a default: a share of a peak is only
meaningful against the chip that ran it.
"""

from __future__ import annotations

from typing import Dict

_V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "TPU v5e, Google Cloud documentation",
}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None
