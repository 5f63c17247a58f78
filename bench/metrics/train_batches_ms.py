"""Local training (``fl/pipeline.py`` ``sample_cohort_batches``): the
``train.batches`` span of ``ctx.timings`` per round, the host's draws and
stacking of the cohort's local batches.  None where the program keeps no
such span."""
import numpy as np

KEY = "train.batches"


def read(rec):
    if rec["kind"] != "round" or not any(KEY in t for t in rec["timings"]):
        return None
    return float(np.mean([t.get(KEY, 0.0) for t in rec["timings"]])) * 1e3
