"""Local training (the trainers' ``dispatch``): the ``train.dispatch``
span of ``ctx.timings`` per round, the batches' transfer to the device
and the training program's launch.  None where the program keeps no such
span."""
import numpy as np

KEY = "train.dispatch"


def read(rec):
    if rec["kind"] != "round" or not any(KEY in t for t in rec["timings"]):
        return None
    return float(np.mean([t.get(KEY, 0.0) for t in rec["timings"]])) * 1e3
