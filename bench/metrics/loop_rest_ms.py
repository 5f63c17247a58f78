"""The round loop (``fl/runtime.py``): a round's wall clock less its
train, validate, pack and aggregate buckets (sampling, election, rewards,
chain bookkeeping)."""
import numpy as np

BUCKETS = ("train", "validate", "pack", "aggregate")


def read(rec):
    if rec["kind"] != "round" or not rec["timings"]:
        return None
    rest = [w - sum(t.get(k, 0.0) for k in BUCKETS)
            for w, t in zip(rec["round_walls"], rec["timings"])]
    return float(np.mean(rest)) * 1e3
