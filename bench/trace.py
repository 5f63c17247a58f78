"""The profiler trace of a run's window, and its reduction to numbers.

``Tracer`` records the window with ``jax.profiler`` (host spans on, Python
tracer off) into a fixed directory inside the checkout, reduces the trace
and deletes it.  The reduction works on plain event lists, so it can be
checked on a small recorded trace:

* device planes are ``/device:TPU:<n>``; their ``XLA Ops`` events are the
  operations (nested ones included), their ``XLA Modules`` events the
  programs, named ``jit_<function>(<hash>)``;
* the host's main-thread line (named after the interpreter) holds the
  benchmark's spans (``window``, ``round``, ``stage.*``, ``serve.*``) and
  JAX's own host events.

Busy time is the union of a device's operation intervals inside the
window, averaged over devices; program and collective times are averaged
over devices the same way.  Each idle gap of device 0 is named by the
innermost host event that covers its middle, under the benchmark span
around it.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute")
_HLO_NAME = re.compile(r"^%?([\w.\-]+)\s*=")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")
BENCH_SPANS = ("window", "round", "stage.", "serve.")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = _HLO_NAME.match(text)
    return m.group(1) if m else text


def module_name(text: str) -> str:
    """``jit_tick(228547805589)`` -> ``jit_tick``."""
    return _MODULE.match(text).group(1)


def union(intervals: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(host: List[Event], t: float) -> str:
    """The innermost host event covering t, under its benchmark span."""
    inner: Optional[Event] = None
    span: Optional[Event] = None
    for ev in host:
        name, s, e = ev
        if name != "window" and s <= t <= e:
            if inner is None or e - s < inner[2] - inner[1]:
                inner = ev
            if name.startswith(BENCH_SPANS) and (
                    span is None or e - s < span[2] - span[1]):
                span = ev
    if inner is None:
        return "host: outside any call"
    if span is None or span is inner:
        return inner[0]
    return f"{span[0]} > {inner[0]}"


def reduce(devices: List[Dict[str, List[Event]]], host: List[Event],
           window: Tuple[float, float]) -> Dict[str, Any]:
    """Per-device ``ops``/``modules`` events and the host's events ->
    the window's busy and idle time, program and collective time, and the
    longest device operations and idle gaps."""
    lo, hi = window
    n = max(1, len(devices))
    busy = 0.0
    modules: Dict[str, List[float]] = {}
    collective = 0.0
    gaps: List[Tuple[float, float]] = []
    for i, dev in enumerate(devices):
        merged = union([(s, e) for _, s, e in dev["ops"]], lo, hi)
        busy += sum(e - s for s, e in merged)
        coll = union([(s, e) for name, s, e in dev["ops"]
                      if COLLECTIVE.search(op_name(name))], lo, hi)
        collective += sum(e - s for s, e in coll)
        for name, s, e in dev["modules"]:
            if s < lo or s > hi:
                continue
            entry = modules.setdefault(module_name(name), [0.0, 0.0])
            entry[0] += e - s
            entry[1] += 1
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2])
                           if e > s), key=lambda g: g[0] - g[1])[:10]
    gaps = [(_label(host, (s + e) / 2), (e - s) * 1e-9) for s, e in gaps]
    mods = {k: [v[0] * 1e-9 / n, v[1] / n] for k, v in modules.items()}
    top = sorted(mods.items(), key=lambda kv: -kv[1][0])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n,
        "collective_s": collective * 1e-9 / n,
        "modules": mods,
        "device_ops": [[k, v[0]] for k, v in top],
        "idle_gaps": [[name, secs] for name, secs in gaps],
    }


def events_from_xplane(path: str):
    """(devices, host events, window) from a profiler ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if re.match(r"^/device:TPU:\d+$", plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            # the main thread's line is named after the interpreter
            # ("python", "python3"): the one that holds the window span
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                if any(name == "window" for name, _, _ in events):
                    host = events
    windows = [(s, e) for name, s, e in host if name == "window"]
    if not windows or not devices:
        seen = {p.name: [l.name for l in p.lines] for p in data.planes}
        raise RuntimeError(f"trace {path}: no window span or no TPU plane; "
                           f"planes and lines: {seen}")
    return devices, host, windows[0]


class Tracer:
    """Traces the window when ``enabled``; ``summary`` is its reduction
    (None untraced).  ``open()``/``close()`` mark the window itself."""

    def __init__(self, enabled: bool, directory: Optional[str] = None):
        from bench.harness import TRACE_DIR

        self.enabled = enabled
        self.dir = directory or TRACE_DIR
        self.summary: Optional[Dict[str, Any]] = None
        self._span = None

    def __enter__(self):
        if self.enabled:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def open(self) -> None:
        if self.enabled:
            import jax

            self._span = jax.profiler.TraceAnnotation("window")
            self._span.__enter__()

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __exit__(self, *exc) -> None:
        if not self.enabled:
            return
        import jax

        jax.profiler.stop_trace()
        if exc[0] is None:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            self.summary = reduce(*events_from_xplane(files[0]))
            self.summary["xplane_bytes"] = os.path.getsize(files[0])
        shutil.rmtree(self.dir, ignore_errors=True)
