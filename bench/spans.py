"""Host spans around the calls into the program, written from the
benchmark's own files.

Each span is a ``jax.profiler.TraceAnnotation``, so in a traced run it
lands in the profiler's host trace on the same clock as the device ops,
and the trace reduction can say what the host was doing in each idle gap.
Untraced, an annotation costs about a microsecond.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax


class SpanStage:
    """A round-pipeline stage wrapped in a span; forwards ``prepare`` and
    hands the context to ``after(kind, ctx)`` once the stage returns."""

    def __init__(self, kind: str, inner: Any,
                 after: Optional[Callable[[str, Any], None]] = None):
        self.kind, self.inner, self.after = kind, inner, after
        if hasattr(inner, "prepare"):
            self.prepare = self._prepare

    def _prepare(self, ctx) -> None:
        with jax.profiler.TraceAnnotation(f"stage.{self.kind}.prepare"):
            self.inner.prepare(ctx)
        if self.after is not None:
            self.after(self.kind + ".prepare", ctx)

    def __call__(self, ctx) -> None:
        with jax.profiler.TraceAnnotation(f"stage.{self.kind}"):
            self.inner(ctx)
        if self.after is not None:
            self.after(self.kind, ctx)


def spanned(name: str, fn: Callable) -> Callable:
    """``fn`` called inside a span named ``name``."""

    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    return call
