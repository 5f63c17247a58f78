"""Host spans: one name on the profiler's clock, host seconds by key.

``span(name, into, key)`` opens a ``jax.profiler.TraceAnnotation``, so in
a profiled run the span lands in the host trace on the same clock as the
device ops and names what the host was doing while the device idled.
When ``into`` is a dict it also adds the span's host seconds
(``time.perf_counter``) to ``into[key]`` (``key`` defaults to ``name``).
A span only times what the host does inside it: it never waits on the
device.  With the profiler off an annotation costs about a microsecond.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Iterator, Optional

import jax


@contextmanager
def span(name: str, into: Optional[Dict[str, float]] = None,
         key: Optional[str] = None) -> Iterator[None]:
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if into is not None:
            k = name if key is None else key
            into[k] = into.get(k, 0.0) + (time.perf_counter() - t0)
