"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, never a
default."""

from __future__ import annotations

from typing import Dict

#: device_kind -> peaks of ONE chip.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, \"TPU v5e\" (per chip)",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown
    kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
