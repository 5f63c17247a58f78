"""Benchmark harness entry: one function per paper table/figure + systems
benchmarks.  Prints ``name,us_per_call,derived`` CSV lines and writes the
kernel rows to ``BENCH_kernels.json`` and the round-loop stage timings to
``BENCH_round.json`` (name -> {us, bytes}) so the perf trajectory is
machine-trackable across PRs.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

from repro.hostdevices import force_host_devices

# round_bench's sharded-engine rows need multiple devices; the flag must
# land before jax initializes its backend (first device query), i.e. before
# any benchmark runs.  An externally-set force_host flag wins.  NOTE: this
# applies to EVERY section (single-device work still runs on device 0, but
# the XLA CPU thread-pool layout differs) — BENCH_kernels.json and
# BENCH_round.json snapshots are regenerated under this environment since
# PR 3; don't compare them against pre-PR-3 single-device numbers.
force_host_devices()

from benchmarks import common
from repro.compile_cache import enable_compile_cache
from benchmarks import (
    committee_ablation,
    consensus_cost,
    fig3_attack_probability,
    fig4_malicious,
    hier_bench,
    kernel_bench,
    roofline,
    round_bench,
    serve_bench,
    storage_opt,
    table1_accuracy,
)

ALL = {
    "fig3_attack_probability": fig3_attack_probability.run,
    "consensus_cost": consensus_cost.run,
    "kernel_bench": kernel_bench.run,
    "round_bench": round_bench.run,
    "hier_bench": hier_bench.run,
    "serve_bench": serve_bench.run,
    "storage_opt": storage_opt.run,
    "table1_accuracy": table1_accuracy.run,
    "fig4_malicious": fig4_malicious.run,
    "committee_ablation": committee_ablation.run,
    "roofline": roofline.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slow)")
    ap.add_argument("--only", default=None, choices=list(ALL))
    args = ap.parse_args()
    enable_compile_cache()

    names = [args.only] if args.only else list(ALL)
    failures = 0
    sections = {}
    for name in names:
        print(f"\n=== {name} ===")
        t0 = time.time()
        common.RESULTS.clear()
        try:
            ALL[name](full=args.full)
            sections[name] = dict(common.RESULTS)
        except Exception:  # noqa: BLE001
            # no sections entry: a partial run must not overwrite the last
            # complete machine-readable snapshot
            failures += 1
            traceback.print_exc()
            print(f"{name},0.0,FAILED")
        print(f"# {name} took {time.time()-t0:.1f}s")

    root = pathlib.Path(__file__).resolve().parent.parent
    if "kernel_bench" in sections:
        out = root / "BENCH_kernels.json"
        out.write_text(json.dumps(sections["kernel_bench"], indent=2) + "\n")
        print(f"# wrote {out}")
    # BENCH_round.json carries the flat round-loop stage timings AND the
    # hierarchical-round memory rows: merge whichever sections ran into the
    # existing snapshot so a --only run of one doesn't drop the other's
    # rows (renamed rows must be pruned by hand — keys merge, not replace)
    ran = [s for s in ("round_bench", "hier_bench") if s in sections]
    if ran:
        out = root / "BENCH_round.json"
        data = json.loads(out.read_text()) if out.exists() else {}
        for section in ran:
            data.update(sections[section])
        out.write_text(json.dumps(data, indent=2) + "\n")
        print(f"# wrote {out}")
    # serving rows live in their own snapshot: same merge discipline as
    # BENCH_round.json so a --only run keeps unrelated rows intact
    if "serve_bench" in sections:
        out = root / "BENCH_serve.json"
        data = json.loads(out.read_text()) if out.exists() else {}
        data.update(sections["serve_bench"])
        out.write_text(json.dumps(data, indent=2) + "\n")
        print(f"# wrote {out}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
