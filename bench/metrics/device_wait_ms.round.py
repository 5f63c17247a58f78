"""The round loop: every ``<stage>.wait`` span of ``ctx.timings`` per
round summed, the part of the round the host spends blocked on the
device; the round's wall clock less this is the host's own time.  None
where the program keeps no such span."""
import numpy as np


def read(rec):
    if rec["kind"] != "round" or not any(k.endswith(".wait")
                                         for t in rec["timings"] for k in t):
        return None
    return float(np.mean([sum(v for k, v in t.items() if k.endswith(".wait"))
                          for t in rec["timings"]])) * 1e3
