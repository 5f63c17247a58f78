"""The paper's experiments: one function per table or figure (accuracy,
attack probability, committee size, consensus and storage cost).  Prints
``name,us_per_call,derived`` CSV lines.  These are CPU runs of the paper's
results, not speed measurements: the chip benchmark is ``bench/``.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Without ``--full`` each experiment runs its preset sized for a CPU host;
``--full`` (and each script's own ``__main__``) runs the paper-scale sweep.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache
from benchmarks import (
    committee_ablation,
    consensus_cost,
    fig3_attack_probability,
    fig4_malicious,
    storage_opt,
    table1_accuracy,
)

ALL = {
    "fig3_attack_probability": fig3_attack_probability.run,
    "consensus_cost": consensus_cost.run,
    "storage_opt": storage_opt.run,
    "table1_accuracy": table1_accuracy.run,
    "fig4_malicious": fig4_malicious.run,
    "committee_ablation": committee_ablation.run,
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
    for name in names:
        print(f"\n=== {name} ===")
        t0 = time.time()
        try:
            ALL[name](full=args.full)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{name},0.0,FAILED")
        print(f"# {name} took {time.time()-t0:.1f}s")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
