"""Driver of the committee-round cells: ``BFLCRuntime.run_round`` back to
back, as ``repro.api.build_runtime`` wires it by default.

Set-up makes the community's data and the model's weights from the seed,
builds one runtime and drives it through its first three rounds, which
compile every program and are recorded for the check.  The window then
runs rounds on that same runtime until the first round that ends after
``seconds``.  After the window the plain reference (``round_ref`` and the
configuration's model) follows the first three rounds from the same
weights on the same inputs, and the run is correct when every compared
number is within its limit and the chain verifies.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

CHECKED_ROUNDS = 3


class Recorder:
    """Keeps what the first rounds consumed and produced: each cohort's
    clients, local batches, updates and score matrix, the committee's
    validation batches, the packed set and the committed model."""

    def __init__(self):
        self.active = True
        self.rounds: List[Dict[str, Any]] = []
        self.calls: Dict[str, int] = {}
        self._cur = None
        self._batches = None

    def wrap_train(self, fn):
        def call(params, xs, ys):
            if self.active:
                self._batches = (xs, ys)
            return fn(params, xs, ys)

        return call

    def after(self, kind: str, ctx) -> None:
        self.calls[kind] = self.calls.get(kind, 0) + 1
        if not self.active:
            return
        if kind == "validator.prepare":
            self._cur = {"committee": list(ctx.round_committee),
                         "val_x": np.asarray(ctx.val_x),
                         "val_y": np.asarray(ctx.val_y), "cohorts": []}
        elif kind == "local_trainer":
            import jax

            n = len(ctx.trainers)
            xs, ys = self._batches
            self._cur["cohorts"].append({
                "trainers": list(ctx.trainers),
                "xs": np.asarray(xs)[:n], "ys": np.asarray(ys)[:n],
                "updates": jax.device_get(ctx.cohort_updates)})
        elif kind == "validator":
            self._cur["cohorts"][-1]["scores"] = np.asarray(
                ctx.cohort_scores)[: len(ctx.trainers)]
        elif kind == "packer":
            self._cur["packed"] = list(ctx.packed_ids)
        elif kind == "aggregator":
            import jax

            self._cur["new_params"] = jax.device_get(ctx.new_params)
            self.rounds.append(self._cur)


def _leaf_gaps(prog, ref, base=None, *, diff: bool) -> List[float]:
    """Per leaf, |prog - ref| (``diff``) or | |prog| - |ref| |, over the
    larger of the reference leaf's norm and the median leaf's.  With
    ``base`` the leaves are changes from it.  Leaves the reference leaves
    still (norm at most a thousandth of the median's) are left out."""
    import jax

    p_leaves = jax.tree.leaves(prog)
    r_leaves = jax.tree.leaves(ref)
    b_leaves = (jax.tree.leaves(base) if base is not None
                else [0.0] * len(r_leaves))
    p = [np.asarray(a, np.float64) - np.asarray(b, np.float64)
         for a, b in zip(p_leaves, b_leaves)]
    r = [np.asarray(a, np.float64) - np.asarray(b, np.float64)
         for a, b in zip(r_leaves, b_leaves)]
    norms = np.array([np.linalg.norm(x) for x in r])
    med = float(np.median(norms))
    gaps = []
    for a, b, n in zip(p, r, norms):
        if n <= 1e-3 * med:
            continue
        gap = (np.linalg.norm(a - b) if diff
               else abs(np.linalg.norm(a) - np.linalg.norm(b)))
        gaps.append(float(gap / max(n, med)))
    return gaps


def _leafwise(prog, ref, base=None, *, diff: bool) -> float:
    """The worst leaf of ``_leaf_gaps``."""
    return max(_leaf_gaps(prog, ref, base, diff=diff), default=0.0)


def _per_client(stacked, n: int):
    import jax

    return [jax.tree.map(lambda x: np.asarray(x)[i], stacked) for i in range(n)]


def packs_matched(rounds: List[Dict[str, Any]],
                  ref_rounds: List[Dict[str, Any]]) -> int:
    """How many of the first rounds packed the same updates as the
    reference did."""
    m = 0
    for rnd, ref in zip(rounds, ref_rounds):
        if sorted(rnd["packed"]) != sorted(ref["packed"]):
            break
        m += 1
    return m


def compare(rounds: List[Dict[str, Any]], ref_rounds: List[Dict[str, Any]],
            params0) -> Dict[str, float]:
    """The compared numbers of a round cell, program (``rounds``) against
    reference (``ref_rounds``), both from ``params0``."""
    first, rfirst = rounds[0], ref_rounds[0]
    c0 = first["cohorts"][0]
    n = len(c0["trainers"])
    ref_u = _per_client(rfirst["updates"], n)
    per_client = [_leafwise(c0["updates"][i], ref_u[i], diff=True)
                  for i in range(n)]
    update_diff = max(per_client)
    update_gap = max(_leafwise(c0["updates"][i], ref_u[i], diff=False)
                     for i in range(n))
    score_diff = float(np.mean(np.abs(np.asarray(c0["scores"], np.float64)
                                      - rfirst["scores"])))
    # packing: how far the reference's median of a packed update lies below
    # that of an accepted update left out
    meds = rfirst["medians"]
    packed = set(first["packed"])
    left = [meds[u] for u in meds if u not in packed]
    inside = [meds[u] for u in packed if u in meds]
    regret = max(0.0, max(left) - min(inside)) if left and inside else 0.0
    block1 = _leaf_gaps(first["new_params"], rfirst["new_params"], params0,
                        diff=True)
    model1 = max(block1, default=0.0)
    # the change after the last checked round, by the median and the worst
    # leaf; and by the worst leaf after the last of the first rounds whose
    # packed sets agree, since a later round may pack another of two
    # near-tied updates and carry a small leaf far apart
    change = _leaf_gaps(rounds[-1]["new_params"],
                        ref_rounds[-1]["new_params"], params0, diff=False)
    same = packs_matched(rounds, ref_rounds)
    m = max(1, same)
    matched = _leafwise(rounds[m - 1]["new_params"],
                        ref_rounds[m - 1]["new_params"], params0, diff=False)
    return {"update_diff": update_diff,
            "update_diff_median": float(np.median(per_client)),
            "update_gap": update_gap,
            "score_diff": score_diff, "topk_regret": regret,
            "model1_diff": model1,
            "model1_median": float(np.median(block1)) if block1 else 0.0,
            "change_gap": max(change, default=0.0),
            "change_gap_median": float(np.median(change)) if change else 0.0,
            "change_gap_matched": matched, "packs_matched": float(same)}


def reference_rounds(model, params0, rounds, rc, *, precision="highest",
                     dtype=None, hook=None):
    """The reference's trajectory over the recorded rounds' inputs, at the
    given matmul precision (and dtype, float32 unless given)."""
    import jax

    from bench.harness import load_module

    ref = load_module("drivers", "round_ref")
    out, params = [], params0
    with jax.default_matmul_precision(precision):
        programs = ref.Programs(model, lr=rc["local_lr"],
                                momentum=rc["momentum"],
                                **({"dtype": dtype} if dtype else {}))
        for rnd in rounds:
            r = ref.run_round(programs, params, rnd, rc, hook=hook)
            out.append(r)
            params = r["new_params"]
    return out


def run(cell: Dict[str, Any], config: Dict[str, Any], traffic: Dict[str, Any],
        *, seed: int, seconds: float, trace: bool, devices, t0: float,
        patch=None) -> Dict[str, Any]:
    import jax

    from bench.harness import CompileCounter, derive_seeds, load_module
    from bench.spans import SpanStage
    from bench.trace import Tracer
    from repro.api import build_runtime
    from repro.fl import femnist_adapter
    from repro.fl.pipeline import default_stage_names, resolve
    from repro.fl.runtime import BFLCConfig

    model = load_module("configs", config.get("reference", config["name"]))
    gen = load_module("traffic", traffic["generator"])
    s_data, s_model, s_rt = derive_seeds(seed, 3)
    width = int(config["width"])
    ds = gen.make_dataset(traffic, s_data)
    rc = gen.round_config(traffic, s_rt)
    params0 = model.init_params(jax.random.PRNGKey(s_model), width)
    params0_host = jax.device_get(params0)

    mesh = None
    if cell["chips"] > 1:
        from repro.launch.mesh import make_round_mesh

        mesh = make_round_mesh(cell["chips"])
    rec = Recorder()
    names = default_stage_names(BFLCConfig(**rc), mesh)
    stages = {k: SpanStage(k, resolve(k, v), rec.after)
              for k, v in names.items()}
    rt = build_runtime(femnist_adapter(width=width), ds, dict(rc),
                       initial_params=params0, stages=stages, mesh=mesh)
    if mesh is None:
        rt._local_train = rec.wrap_train(rt._local_train)
    else:
        rt._sharded_train = rec.wrap_train(rt._sharded_train)
    if patch is not None:          # a planted fault (tests only)
        patch(rt)
    period = rt.chain.period
    for _ in range(CHECKED_ROUNDS):
        rt.run_round()
    rec.active = False
    calls0 = dict(rec.calls)

    counter = CompileCounter()
    walls, timings, failed, raised = [], [], 0, 0
    tracer = Tracer(trace)
    with tracer:
        counter.active = True
        t_open = time.perf_counter()
        tracer.open()
        while True:
            t = time.perf_counter()
            height = rt.chain.height
            try:
                with jax.profiler.TraceAnnotation("round"):
                    rt.run_round()
                    jax.block_until_ready(rt.global_params())
            except Exception:  # a round that raises fails; stop there
                import traceback

                traceback.print_exc()
                raised = 1
                break
            walls.append(time.perf_counter() - t)
            timings.append(dict(rt.stage_timings[-1]))
            if rt.chain.height != height + period:
                failed += 1
            if time.perf_counter() - t_open >= seconds:
                break
        t_close = time.perf_counter()
        tracer.close()
        counter.active = False
    attempted = len(walls) + raised
    failed += raised
    window_calls = {k: rec.calls.get(k, 0) - calls0.get(k, 0)
                    for k in rec.calls}

    from bench.harness import device_info

    device = device_info(devices)
    blocks = rt.chain.blocks[-len(walls) * period:] if walls else []
    chain_bytes = sum(int(getattr(l, "nbytes", np.asarray(l).nbytes))
                      for b in blocks if b.payload is not None
                      for l in jax.tree.leaves(b.payload))
    verified = rt.chain.verify()
    if not verified:
        failed = attempted
    del rt, stages
    rounds = rec.rounds

    t_ref = time.perf_counter()
    ref_rounds = reference_rounds(model, params0_host, rounds, rc)
    numbers = compare(rounds, ref_rounds, params0_host)
    t_ref = time.perf_counter() - t_ref
    numbers["chain_faults"] = 0.0 if verified else 1.0
    limits = dict(cell["limits"], chain_faults=0.0)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()
              if k in limits}

    P = len(rounds[0]["cohorts"][0]["trainers"])
    record = {
        "kind": "round", "seconds": seconds, "t0": t0, "t_open": t_open,
        "t_close": t_close, "setup_s": t_open - t0,
        "window_s": t_close - t_open, "round_walls": walls,
        "timings": timings, "calls": window_calls, "chain_bytes": chain_bytes,
        "trace": tracer.summary, "chips": cell["chips"],
        "P": P, "Q": len(rounds[0]["committee"]), "K": rc["k_updates"],
        "steps": rc["local_steps"], "batch": rc["local_batch"],
        "val_batch": rc["val_batch"], "int8": bool(rc["quantize_chain"]),
        "dim": int(sum(np.asarray(l).size
                       for l in jax.tree.leaves(params0_host))),
        "fwd_flops": model.forward_flops_per_image(width),
        "train_flops": model.train_flops_per_image(width),
        "device": device, "compiles_in_window": counter.count,
    }
    return {"record": record, "checks": checks, "attempted": attempted,
            "failed": failed, "numbers": numbers, "rounds": rounds,
            "ref_rounds": ref_rounds, "params0": params0_host, "rc": rc,
            "model": model,
            "notes": {"compilations in window": counter.count,
                      "numbers": numbers,
                      "rounds in window": len(walls),
                      "round wall min/median/max (s)": [
                          round(float(f(walls)), 4) if walls else None
                          for f in (np.min, np.median, np.max)],
                      "window calls": window_calls,
                      "reference seconds": round(t_ref, 3)}}
