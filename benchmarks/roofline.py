"""Roofline table: reads the dry-run records in experiments/dryrun/*.json and
prints the three-term analysis per (arch x shape x mesh) for EXPERIMENTS.md
§Roofline."""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit

DRYRUN_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "experiments", "dryrun",
)

SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,
    "long_500k": 1,
}


def model_flops(rec) -> float:
    """6*N*D (dense) or 6*N_active*D per step; decode = 2*N_active per token."""
    tokens = SHAPE_TOKENS[rec["shape"]]
    n = rec.get("active_params") or rec.get("params") or 0
    if rec["shape"].startswith(("decode", "long")):
        return 2.0 * n * tokens
    return 6.0 * n * tokens


def run(full: bool = False, tag: str = None):
    if tag is None:
        out = []
        for t in ("baseline", "opt"):
            out += run(full=full, tag=t) or []
        return out
    files = sorted(glob.glob(os.path.join(DRYRUN_DIR, f"*_{tag}.json")))
    if not files:
        print(f"# no '{tag}' dry-run records under {DRYRUN_DIR}")
        emit(f"roofline_{tag}", 0.0, "no_records")
        return []
    print(f"# [{tag}] roofline terms per (arch, shape, mesh) — s/step/device")
    print("arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
          "model_tflops,hlo_tflops,useful_ratio,peak_mem_GB")
    rows = []
    for f in files:
        rec = json.load(open(f))
        if rec.get("skipped") or rec.get("error"):
            continue
        r = rec["roofline"]
        chips = rec["chips"]
        mf = model_flops(rec) / chips           # per device
        hf = rec["flops_per_device"]
        ratio = mf / hf if hf else 0.0
        pm = (rec.get("peak_memory_per_device") or 0) / 1e9
        rows.append(rec)
        print(
            f"{rec['arch']},{rec['shape']},{rec['mesh']},"
            f"{r['compute_s']:.4f},{r['memory_s']:.4f},"
            f"{r['collective_s']:.4f},{r['dominant']},"
            f"{mf/1e12:.2f},{hf/1e12:.2f},{ratio:.2f},{pm:.2f}"
        )
    emit(f"roofline_{tag}", 0.0, f"records={len(rows)}")
    return rows


if __name__ == "__main__":
    run(full=True)
