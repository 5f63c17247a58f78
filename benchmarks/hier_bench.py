"""Hierarchical-round scaling: peak update-stack bytes vs community size.

Sweeps the simulated community size P from 32 to 100k+ clients through the
two-tier round engine (``repro.fl.hier``, ``build_runtime(..., tiers=S)``)
and reports the measured high-water mark of update-stack bytes held at once
(``HierState.peak_stack_bytes``) against the O(P·D) stack a flat round
would materialize (``flat_stack_bytes``).  The point of the subsystem is
that the peak is bounded by the largest *slice* (~``SLICE`` trainers), not
by P — the rows make that bound a tracked number.

Large P is simulated with ``VirtualFederatedDataset``: virtual client ``i``
aliases base shard ``i % 32`` (no data copies), so the sweep measures the
round engine's behaviour — slicing, streaming ingest, per-slice fused int8
consensus, the tier-2 committee round — at 100k clients without 100k
shards.  Each P runs the full quantized sharded engine: int8 chain blobs,
fused score-from-int8 tier-1 validation (the row-quant cache feeds the
sub-aggregation), shard_mapped training over the forced host devices.

Wall-clock per round is reported too (first round, so XLA compilation is
included — these rows track memory scaling, not steady-state latency; the
round's per-stage host time is ``BFLCRuntime.stage_timings``).

``benchmarks.run`` merges these rows into ``BENCH_round.json``.
Standalone CLI (the CI bench smoke step runs ``--smoke``):

  PYTHONPATH=src python -m benchmarks.hier_bench --smoke
  PYTHONPATH=src python -m benchmarks.hier_bench --full   # adds P=102400
"""
from __future__ import annotations

import math
import time

from benchmarks.common import emit

SLICE = 256   # target tier-1 slice width (trainers + sub-committee)
Q2 = 4        # round (tier-2) committee size, held fixed across the sweep


def _tiers_for(pool: int) -> int:
    """S sized so each slice holds ~SLICE nodes (>= 2: the tiered engine's
    floor; the partitioner needs 4 nodes per slice)."""
    return max(2, math.ceil(pool / SLICE))


def run(full: bool = False, rounds: int | None = None, smoke: bool = False):
    import jax

    from repro.api import build_runtime
    from repro.data import VirtualFederatedDataset, make_femnist_like
    from repro.fl import femnist_adapter
    from repro.launch.mesh import make_round_mesh

    rounds = 1 if rounds is None else rounds
    sweep = ((32, 256) if smoke
             else (32, 1024, 10240) + ((102400,) if full else ()))
    # 32 base shards aliased by every virtual community in the sweep
    base = make_femnist_like(num_clients=32, mean_samples=40, test_size=64,
                             seed=5)
    adapter = femnist_adapter(width=2)
    ndev = min(8, len(jax.devices()))
    mesh = make_round_mesh(ndev) if ndev > 1 else None

    print("# hierarchical rounds: peak update-stack bytes (nbytes column) "
          "vs flat O(P*D) equivalent, fused int8 engine, "
          f"ndev={ndev}, slice~{SLICE}")
    print("hier_P,us_per_round")
    for P in sweep:
        ds = VirtualFederatedDataset(base, P)
        S = _tiers_for(P - Q2)
        cfg = dict(
            active_proportion=1.0,           # every virtual client trains
            committee_fraction=Q2 / P,       # q_committee = Q2, q_sub >= 3
            k_updates=8,
            local_steps=1, local_batch=8, val_batch=16,
            quantize_chain=True, use_kernels=True,
            seed=0,
        )
        inner = "committee_int8_sharded" if mesh is not None else \
            "committee_int8"
        rt = build_runtime(adapter, ds, cfg, mesh=mesh, tiers=S,
                           stages={"validator": inner})
        t0 = time.perf_counter()
        rt.run(rounds, eval_every=rounds + 1)
        us = (time.perf_counter() - t0) / rounds * 1e6
        assert rt.chain.verify()
        log = rt.hier_logs[-1]
        peak, flat = log["peak_stack_bytes"], log["flat_stack_bytes"]
        emit(
            f"hier_P{P}", us,
            derived=(f"S={S};slice_rows={log['max_slice_rows']};"
                     f"flat_bytes={flat};ratio={flat / max(peak, 1):.1f};"
                     f"t1_validations={log['t1_validations']};"
                     f"rounds={rounds};compile_included=1"),
            nbytes=peak,
        )
        # the claimed bound: the peak is one slice's padded stack (+ the
        # S sub-aggregate blocks at tier 2), never the O(P*D) flat stack
        if P >= 1024:
            assert peak < flat, (P, peak, flat)


if __name__ == "__main__":
    import argparse

    # forced host devices for the sharded engine, set before jax touches
    # its backend (module imports above don't query devices)
    from repro.hostdevices import force_host_devices

    force_host_devices()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="adds the 102400-client row (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sanity scale: P=32 and P=256 only")
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds per community size (default 1)")
    ap.add_argument("--out", default=None,
                    help="also write the emitted rows as JSON (the CI "
                         "smoke step uploads this)")
    args = ap.parse_args()
    run(full=args.full, rounds=args.rounds, smoke=args.smoke)
    if args.out:
        import json

        from benchmarks.common import RESULTS

        with open(args.out, "w") as f:
            json.dump(RESULTS, f, indent=2)
        print(f"# wrote {args.out}")
