"""Shared helpers for the paper's experiments."""
from __future__ import annotations

import time
from typing import Callable


def _block(out):
    """Wait for async jax work (non-jax results pass through); a device
    error raises here rather than ending up inside a timing."""
    import jax

    return jax.block_until_ready(out)


def time_us(fn: Callable, *args, warmup: int = 2, iters: int = 10) -> float:
    # block on every warmup result so compilation + warmup compute finish
    # before the timed window opens (async dispatch would otherwise bleed
    # warmup work into — or hide timed work from — the measurement)
    for _ in range(warmup):
        _block(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _block(out)
    return (time.perf_counter() - t0) / iters * 1e6


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}")
