"""Readings that set a cell's limits: the program's, the lower-precision
control's and, for round cells, those of planted faults.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs the cell as ``bench/run.py`` does (a short window),
then puts the reference in the program's place: at the matmul precision
``high`` (the control: three bfloat16 passes, the step below the
configurations' float32 at ``highest``) and at ``highest`` with one
fault planted (round cells): the aggregate over half the packed updates,
one client's update negated where it is produced, the model block left
unchanged, and on several chips the updates of every chip but the first
left out.  Each is compared with the float32 reference by the cell's own
numbers.  Serving cells plant their fault in the program instead: every
decoded token altered where the tick produces it, in the first three
seeds.  One JSON line per seed; the benchmark's runs never run this.
``--rates`` instead serves the cell's mix at each offered rate (the knee
sweep) and prints the end-to-end metrics per rate.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time


# the precision below the configurations' float32 at "highest": three
# bfloat16 passes per float32 product
CONTROL = "high"
# serving: the altered-token fault is planted in the first three seeds
FAULT_SEEDS = 3


def round_readings(out, chips: int, control_dtype=None):
    """Control and fault readings of a round run; ``control_dtype`` puts
    the control in that dtype too (where the precision flag has no effect,
    as on the CPU)."""
    from bench.harness import load_module

    drv = load_module("drivers", "round")
    model, params0, rc = out["model"], out["params0"], out["rc"]
    rounds, ref = out["rounds"], out["ref_rounds"]
    k = rc["k_updates"]

    def as_program(ref_rounds):
        prog = []
        for r, rnd in zip(ref_rounds, rounds):
            n = len(rnd["cohorts"][0]["trainers"])
            prog.append({"cohorts": [{"trainers": rnd["cohorts"][0]["trainers"],
                                      "updates": drv._per_client(r["updates"], n),
                                      "scores": r["scores"]}],
                         "packed": r["packed"], "new_params": r["new_params"]})
        return prog

    def with_fault(hook):
        return drv.compare(as_program(drv.reference_rounds(
            model, params0, rounds, rc, hook=hook)), ref, params0)

    def negate_first(name, value, params):
        if name != "updates":
            return value
        return {k2: {k3: v.at[0].multiply(-1.0) for k3, v in d.items()}
                for k2, d in value.items()}

    def half(name, value, params):
        if name != "packed":
            return value
        ids, meds = value
        return ids[: k // 2], meds[: k // 2]

    def unchanged(name, value, params):
        return params if name == "new_params" else value

    def first_chip_only(name, value, params):
        if name != "updates":
            return value
        import jax

        def keep(x):
            n = x.shape[0]
            share = -(-n // chips)
            return x.at[share:].set(0.0)

        return jax.tree.map(keep, value)

    readings = {
        "control": drv.compare(as_program(drv.reference_rounds(
            model, params0, rounds, rc, precision=CONTROL,
            dtype=control_dtype)), ref, params0),
        "half_batch": with_fault(half),
        "answer_altered": with_fault(negate_first),
        "state_unchanged": with_fault(unchanged),
    }
    if chips > 1:
        readings["exchange_left_out"] = with_fault(first_chip_only)
    return readings


def round_look(out):
    """Per checked round: whether the program packed the same updates as
    the reference, how many of its packed updates the reference left out,
    the widest gap between their medians, and the worst leaf of the
    model's change (gap of norms, as ``change_gap``) with its name."""
    import jax
    import numpy as np

    from bench.harness import load_module

    rounds, ref, params0 = out["rounds"], out["ref_rounds"], out["params0"]
    named = jax.tree_util.tree_flatten_with_path(params0)[0]
    names = [jax.tree_util.keystr(path) for path, _ in named]
    base = [np.asarray(x, np.float64) for _, x in named]
    look = []
    for rnd, r in zip(rounds, ref):
        meds = {u: float(np.median(c["scores"][i]))
                for c in rnd["cohorts"] for i, u in enumerate(c["trainers"])}
        p = [np.asarray(x, np.float64) - b
             for x, b in zip(jax.tree.leaves(rnd["new_params"]), base)]
        q = [np.asarray(x, np.float64) - b
             for x, b in zip(jax.tree.leaves(r["new_params"]), base)]
        norms = np.array([np.linalg.norm(x) for x in q])
        med = float(np.median(norms))
        gaps = [abs(np.linalg.norm(a) - n) / max(n, med)
                for a, n in zip(p, norms)]
        worst = int(np.argmax(gaps))
        look.append({
            "same_pack": sorted(rnd["packed"]) == sorted(r["packed"]),
            "left_out": len(set(rnd["packed"]) - set(r["packed"])),
            "median_gap": max(abs(meds[u] - r["medians"][u]) for u in meds),
            "worst_leaf": names[worst], "worst_gap": float(gaps[worst])})
    # round 1, client by client: the worst leaf of ||u - r|| / ||r||
    drv = load_module("drivers", "round")
    c0 = rounds[0]["cohorts"][0]
    ref_u = drv._per_client(ref[0]["updates"], len(c0["trainers"]))
    look.append({"update_diffs": [
        round(drv._leafwise(u, r, diff=True), 6)
        for u, r in zip(c0["updates"], ref_u)]})
    return look


def serve_readings(out, control_dtype=None):
    """The control's widest logit gap over the run's sampled requests and
    its keys' and values' distance over the requests the slots held."""
    from bench.harness import load_module

    drv = load_module("drivers", "serve")
    args = (out["model"], out["weights"], out["config"])
    max_len = int(out["config"]["serving"]["max_len"])
    gaps = drv.reference_gaps(*args, out["samples"], max_len,
                              control_precision=CONTROL,
                              control_dtype=control_dtype)
    kv = drv.kv_gaps(*args, out["held"], max_len, control_precision=CONTROL,
                     control_dtype=control_dtype)
    return {"control": {"logit_gap": max(float(g.max()) for g in gaps),
                        "kv_diff": max(kv.values())}}


def token_altered(eng):
    """A fault planted in the program: every decoded token is replaced by
    the next id where the tick produces it."""
    inner = eng._tick

    def tick(params, tokens, positions, cache):
        tok, pos, cache = inner(params, tokens, positions, cache)
        return (tok + 1) % eng.cfg.vocab_size, pos, cache

    eng._tick = tick


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", default="",
                    help="comma-separated offered rates: the knee sweep")
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
    if args.rates:
        for rate, seed in zip([float(r) for r in args.rates.split(",")],
                              seeds * 100):
            res, out = harness.run_cell(
                args.workload, seed=seed, seconds=args.seconds, trace=False,
                devices=devices, t0=time.perf_counter(), bench=bench,
                root=root, traffic_overrides={"rate": rate})
            print(json.dumps({"rate": rate, "seed": seed,
                              "correct": res["correct"],
                              "metrics": {k: v["value"] for k, v in
                                          res["metrics"].items()},
                              "window_s": out["record"]["window_s"],
                              "numbers": out["numbers"]}), flush=True)
            del res, out           # one engine's weights on the chip at a time
            gc.collect()
        return 0
    for seed in seeds:
        res, out = harness.run_cell(args.workload, seed=seed,
                                    seconds=args.seconds, trace=False,
                                    devices=devices, t0=t0, bench=bench,
                                    root=root)
        line = {"seed": seed, "correct": res["correct"],
                "program": out["numbers"]}
        serving = out["record"]["kind"] == "serve"
        if not serving:
            line.update(round_readings(out, cell["chips"]),
                        look=round_look(out))
        else:
            line.update(serve_readings(out))
        line.update(metrics={k: v["value"] for k, v in res["metrics"].items()},
                    device=res["device"], notes=out["notes"])
        del res, out
        gc.collect()
        if serving and seed in seeds[:FAULT_SEEDS]:
            _, out = harness.run_cell(
                args.workload, seed=seed, seconds=args.seconds, trace=False,
                devices=devices, t0=time.perf_counter(), bench=bench,
                root=root, patch=token_altered)
            line["answer_altered"] = out["numbers"]
            del out
            gc.collect()
        print(json.dumps(line, default=str), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
