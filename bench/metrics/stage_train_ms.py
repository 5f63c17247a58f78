"""Local training (``fl/client.py``): the ``train`` stage bucket of
``ctx.timings`` per round, host clock, batch sampling included."""
import numpy as np


def read(rec):
    if rec["kind"] != "round" or not rec["timings"]:
        return None
    return float(np.mean([t.get("train", 0.0) for t in rec["timings"]])) * 1e3
