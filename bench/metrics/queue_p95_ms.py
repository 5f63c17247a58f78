"""The scheduler: 95th percentile of the wait from due arrival to
admission into a slot."""
import numpy as np


def read(rec):
    if rec["kind"] != "serve":
        return None
    t = [r["admitted"] - r["arrival"] for r in rec["requests"]
         if r["admitted"] >= 0]
    return float(np.percentile(t, 95)) * 1e3 if t else None
