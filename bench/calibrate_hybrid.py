"""Readings that set a hybrid serving cell's limits: the program's, the
lower-precision control's and those of faults planted in the program.

    python bench/calibrate_hybrid.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed it runs the cell as ``bench/run.py`` does (a short window),
then puts the reference at the matmul precision ``high`` (the control: three
bfloat16 passes, the step below the configuration's float32 at ``highest``)
in the program's place over the same requests, and gives the harness's
``correct`` verdict on the control's numbers against the cell's limits.  In
the first seeds it runs the cell again with a fault planted in the program,
with the harness's verdict: each admitted request's SSM state zeroed on
insert, and the pad steps of the chunked scan run on the last step's inputs
instead of carrying the state.  One JSON line per seed; the benchmark's
runs never run this.  ``bench/calibrate.py --rates`` is the
knee sweep for these cells too.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

CONTROL = "high"
FAULT_SEEDS = 1


def control_readings(out, control_dtype=None):
    """The control's widest logit gap over the run's sampled requests, its
    keys' and values' distance over the requests the slots held, and its
    states' distance over the sampled requests."""
    from bench.harness import load_module

    drv = load_module("drivers", "serve_hybrid")
    args = (out["model"], out["weights"], out["config"])
    max_len = int(out["config"]["serving"]["max_len"])
    gaps = drv.serve.reference_gaps(*args, out["samples"], max_len,
                                    control_precision=CONTROL,
                                    control_dtype=control_dtype)
    reqs = {r.rid: r for r in out["samples"] + list(out["held"].values())}
    kv, state = drv.state_kv_gaps(*args, reqs, max_len,
                                  control_precision=CONTROL,
                                  control_dtype=control_dtype)
    held = {r.rid for r in out["held"].values()}
    return {"logit_gap": max(float(g.max()) for g in gaps),
            "kv_diff": max(v for k, v in kv.items() if k in held),
            "state_diff": max(state[r.rid] for r in out["samples"])}


def state_zeroed(eng):
    """A fault: every admitted request's SSM state is zeroed on insert."""
    import jax.numpy as jnp

    inner = eng._insert

    def insert(cache, tokens, positions, slot_cache, first_tok, pos0, b):
        units = tuple(dict(c, ssm=jnp.zeros_like(c["ssm"])) if "ssm" in c
                      else c for c in slot_cache["units"])
        return inner(cache, tokens, positions,
                     dict(slot_cache, units=units), first_tok, pos0, b)

    eng._insert = insert


@contextlib.contextmanager
def pad_steps_run():
    """A fault, for the programs traced inside it: the chunked scan's pad
    steps repeat the last step's inputs, so they advance the state."""
    import jax.numpy as jnp

    from repro.models import mamba

    kept = mamba._time_pad
    mamba._time_pad = lambda t, pad: (
        jnp.pad(t, ((0, 0), (0, pad), (0, 0)), mode="edge") if pad else t)
    try:
        yield
    finally:
        mamba._time_pad = kept


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, root)
    from bench import harness

    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    bench = harness.load_benchmark(root)
    cell = harness.cell_spec(args.workload, bench, root)
    devices = jax.devices()[: cell["chips"]]
    seeds = [int(s) for s in args.seeds.split(",") if s]

    def run(seed, patch=None):
        return harness.run_cell(args.workload, seed=seed,
                                seconds=args.seconds, trace=False,
                                devices=devices, t0=time.perf_counter(),
                                bench=bench, root=root, patch=patch)

    def fault(run_out):
        res, out = run_out
        return dict(out["numbers"], correct=res["correct"])

    for seed in seeds:
        res, out = harness.run_cell(args.workload, seed=seed,
                                    seconds=args.seconds, trace=False,
                                    devices=devices, t0=t0, bench=bench,
                                    root=root)
        control = control_readings(out)
        verdict = harness.checks_pass(
            {k: {"value": v, "limit": cell["limits"][k]}
             for k, v in control.items()})
        line = {"seed": seed, "correct": res["correct"],
                "program": out["numbers"],
                "control": dict(control, correct=verdict),
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "device": res["device"], "notes": out["notes"]}
        del res, out
        gc.collect()
        if seed in seeds[:FAULT_SEEDS]:
            line["state_zeroed"] = fault(run(seed, state_zeroed))
            gc.collect()
            with pad_steps_run():
                line["pad_steps_run"] = fault(run(seed))
            gc.collect()
        print(json.dumps(line, default=str), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
