"""Committee scoring (``fl/client.py`` scorers): the ``validate`` stage
bucket per round, host clock."""
import numpy as np


def read(rec):
    if rec["kind"] != "round" or not rec["timings"]:
        return None
    return float(np.mean([t.get("validate", 0.0) for t in rec["timings"]])) * 1e3
