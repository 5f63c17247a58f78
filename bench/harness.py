"""Finding cells, configurations, mixes and metrics by name, and printing a
run's result.

A cell is an entry of ``BENCHMARK.json`` ``workloads`` plus its file
``bench/workloads/<cell>.json`` (the entry point it drives, its limits).
Its configuration is ``bench/configs/<config>.json`` with the plain
reference ``bench/configs/<config>.py`` beside it; its traffic mix is
``bench/traffic/<traffic>.json``, read by the generator
``bench/traffic/<generator>.py`` that the mix names; its driver is
``bench/drivers/<driver>.py``; and every metric is read from the run's
record by ``bench/metrics/<metric>.py``.  Adding any of these is adding
files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# fixed paths inside the checkout: the compile cache's key includes its path
CACHE_DIR = os.path.join(ROOT, ".cache", "bench-jax")
TRACE_DIR = os.path.join(ROOT, ".cache", "bench-trace")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _path(kind: str, name: str, ext: str, root: str = ROOT) -> str:
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return os.path.join(root, "bench", kind, name + ext)


def load_json(kind: str, name: str, root: str = ROOT) -> Dict[str, Any]:
    path = _path(kind, name, ".json", root)
    if not os.path.exists(path):
        raise KeyError(f"no {kind} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold '-' and '.')."""
    path = _path(kind, name, ".py", root)
    if not os.path.exists(path):
        raise KeyError(f"no {kind} module named {name!r} ({path})")
    modname = "bench_%s_%s" % (kind, re.sub(r"\W", "_", name))
    if modname in sys.modules and sys.modules[modname].__file__ == path:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_spec(name: str, bench: Dict[str, Any], root: str = ROOT
              ) -> Dict[str, Any]:
    """The cell's ``BENCHMARK.json`` entry merged with its own file, which
    has to name the same configuration, traffic and chips."""
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    entry = entries[0]
    own = load_json("workloads", name, root)
    for key in ("config", "traffic", "chips"):
        if own.get(key) != entry[key]:
            raise ValueError(f"workload {name!r}: {key} is {entry[key]!r} in "
                             f"BENCHMARK.json but {own.get(key)!r} in its file")
    return dict(own, name=name)


def metric_entries(bench: Dict[str, Any], cell: str, trace: bool
                   ) -> List[Dict[str, Any]]:
    """The metrics a run of ``cell`` reports: end to end untraced, per layer
    traced; a metric with a ``workloads`` list only in those cells."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def read_metrics(entries: List[Dict[str, Any]], rec: Dict[str, Any],
                 *, required: bool, root: str = ROOT) -> Dict[str, Any]:
    """Each metric's reader over the run's record.  A reader that finds
    nothing returns None and the metric is left out, unless it is
    ``required`` (the end-to-end metrics)."""
    out = {}
    for m in entries:
        value = load_module("metrics", m["name"], root).read(rec)
        if value is None:
            if required:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_info(devices) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them, and the peak memory on
    the fullest chip: the larger of the peak in use and the peak reserved,
    which holds the compiled programs' temporary buffers."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts the executables JAX builds while active: each compilation,
    and each load from the persistent cache."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.active = False
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if self.active and event == self._EVENT:
            self.count += 1


def derive_seeds(seed: int, n: int) -> List[int]:
    """n independent 32-bit seeds from one seed of any size."""
    import numpy as np

    return [int(x) for x in np.random.SeedSequence(int(seed)).generate_state(n)]


def run_cell(name: str, *, seed: int, seconds: float, trace: bool, devices,
             t0: float, bench: Optional[Dict[str, Any]] = None,
             root: str = ROOT, patch=None, traffic_overrides=None):
    """One run of a cell on ``devices``: the driver's window and check, then
    the metrics.  Returns (result line, the driver's output)."""
    bench = bench if bench is not None else load_benchmark(root)
    cell = cell_spec(name, bench, root)
    config = load_json("configs", cell["config"], root)
    traffic = dict(load_json("traffic", cell["traffic"], root),
                   **(traffic_overrides or {}))
    driver = load_module("drivers", cell["driver"], root)
    import jax

    # the program runs at the matmul precision its configuration states
    with jax.default_matmul_precision(config.get("matmul_precision")):
        out = driver.run(cell, config, traffic, seed=seed, seconds=seconds,
                         trace=trace, devices=devices, t0=t0, patch=patch)
    rec = out["record"]
    metrics = read_metrics(metric_entries(bench, name, trace), rec,
                           required=not trace, root=root)
    device = dict(rec["device"])
    result = {"correct": checks_pass(out["checks"]) and out["failed"] == 0,
              "attempted": int(out["attempted"]), "failed": int(out["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        summary = rec["trace"]
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    return result, out


def check_line(checks: Dict[str, Dict[str, float]]) -> Dict[str, Any]:
    """Every compared number beside its limit; passes when value <= limit."""
    return {k: {"value": float(v["value"]), "limit": float(v["limit"])}
            for k, v in checks.items()}


def checks_pass(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]],
         notes: Optional[Dict[str, Any]] = None) -> None:
    """Notes and the compared numbers on stderr (the numbers last), then the
    result as the last line of stdout, its ``checks`` key last."""
    for k, v in (notes or {}).items():
        print(f"# {k}: {v}", file=sys.stderr)
    for k, v in check_line(checks).items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = check_line(checks)
    print(json.dumps(line), flush=True)
