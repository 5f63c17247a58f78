"""Forced host-device bootstrap — the one place the device count lives.

The sharded round engine needs multiple devices; on CPU-only hosts XLA
fakes them via ``--xla_force_host_platform_device_count``.  The flag is
only read when jax initializes its backend, so callers (tests/conftest.py)
must invoke this before anything touches jax — which is also why this module
must never import jax itself.
"""
from __future__ import annotations

import os

FORCE_FLAG = "--xla_force_host_platform_device_count"
DEFAULT_HOST_DEVICES = 8


def force_host_devices(n: int = DEFAULT_HOST_DEVICES) -> None:
    """Idempotently append ``--xla_force_host_platform_device_count=n`` to
    ``XLA_FLAGS``.  An externally-provided force_host flag wins (CI matrix,
    local experiments)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if FORCE_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {FORCE_FLAG}={n}".strip()
