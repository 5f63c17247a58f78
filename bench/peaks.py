"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark keeps its own copy so that no change to the program can move
the yardstick.  A device kind that is not listed is an error, not a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "source": 'Google Cloud documentation, "TPU v5e"',
        "flops_bf16": 197e12,       # FLOP/s
        "ops_int8": 393e12,         # OP/s
        "hbm_bytes_s": 819e9,       # bytes/s
        "hbm_bytes": 16e9,          # bytes of HBM per chip
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        "ici_link_bytes_s": 50e9,
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    """The ``PEAKS`` entry for a device kind; raises for one not listed."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
