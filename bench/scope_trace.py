"""Device time inside the selective scan of the program, read from the
profiler trace of the window, and the prompts of the prefill calls that the
trace kept.

``ScopeTracer`` is ``bench.trace.Tracer`` that, before the trace is deleted,
also sums the device time of the scan's operations on device 0 over the
calls of one program, and counts the calls.  The program names the scan
with ``jax.named_scope("mamba.scan")``, but a v5e trace keeps no scope with
its operations (their events carry only times), so the operations whose HLO
text holds the scan state's shape stand in for the scope: the scan's
``while`` loops, whose loop state holds it, and the steps that read and
write it.  The union of their intervals is taken, so an operation nested in
another (a loop's body inside the loop) counts once.

The scan runs about ten operations a step, so a window of long prompts can
fill the profiler's buffers before it closes: the trace then keeps the
window's first seconds only.  ``ScopeTracer`` reduces such a trace over the
part it kept (its ``window_s`` and ``busy_s`` are that part's) and records
the whole window under ``truncated``.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
from typing import Any, Dict, List, Optional

from bench import trace
from bench.trace import Event

# device 0 idle this long before the window closes: the trace ran out.  A
# serving window closes right after its last tick.
TRUNCATED_S = 1.0


def scope_reduce(calls: List[Event], ops: List[Event], window,
                 module: str, state_shape: str) -> Dict[str, Any]:
    """Over device 0's program events ``calls`` and operation events
    ``ops``: the seconds of the operations whose text holds
    ``state_shape``, inside the calls of ``module`` that start in
    ``window``, and the number of those calls."""
    lo, hi = window
    spans = sorted((s, e) for name, s, e in calls
                   if trace.module_name(name) == module and lo <= s <= hi)
    starts = [s for s, _ in spans]

    def inside(s, e) -> bool:
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= spans[i][1]

    picked = [(s, e) for name, s, e in ops
              if state_shape in name and inside(s, e)]
    return {"scope_s": sum(e - s for s, e in trace.union(picked, lo, hi))
            * 1e-9, "calls": len(spans)}


def kept_window(ops: List[Event], window):
    """(the part of ``window`` that device 0's operations cover up to their
    last end, or the whole window, and whether the trace ran out)."""
    lo, hi = window
    last = max((e for _, _, e in ops), default=hi)
    if hi - last > TRUNCATED_S * 1e9:
        return (lo, last), True
    return window, False


def prefill_prompts(rec) -> List[int]:
    """The prompt lengths of the window's prefill calls, in the order the
    engine made them: one per admitted request, in order of admission."""
    admitted = sorted((r["admitted"], i) for i, r in enumerate(rec["requests"])
                      if r["admitted"] >= 0)
    return [rec["requests"][i]["prompt_len"] for _, i in admitted]


class ScopeTracer(trace.Tracer):
    """``Tracer`` whose ``summary`` also holds ``scope``: the scan's device
    time over the calls of ``module`` (see ``scope_reduce``), reduced over
    the part of the window the trace kept (see ``kept_window``)."""

    def __init__(self, enabled: bool, *, module: str, state_shape: str,
                 directory: Optional[str] = None):
        super().__init__(enabled, directory)
        self.module, self.state_shape = module, state_shape

    def __exit__(self, *exc) -> None:
        if not self.enabled:
            return
        import jax

        jax.profiler.stop_trace()
        if exc[0] is None:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            devices, host, window = trace.events_from_xplane(path)
            kept, truncated = kept_window(devices[0]["ops"], window)
            self.summary = trace.reduce(devices, host, kept)
            self.summary["xplane_bytes"] = os.path.getsize(path)
            self.summary["scope"] = scope_reduce(
                devices[0]["modules"], devices[0]["ops"], kept,
                self.module, self.state_shape)
            if truncated:
                self.summary["truncated"] = {
                    "window_s": (window[1] - window[0]) * 1e-9,
                    "kept_s": self.summary["window_s"]}
        shutil.rmtree(self.dir, ignore_errors=True)
