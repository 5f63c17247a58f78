"""Packing and aggregation (``fl/pipeline.py`` packers and aggregators,
``kernels/ops.py``): the ``pack`` plus ``aggregate`` buckets per round."""
import numpy as np


def read(rec):
    if rec["kind"] != "round" or not rec["timings"]:
        return None
    return float(np.mean([t.get("pack", 0.0) + t.get("aggregate", 0.0)
                          for t in rec["timings"]])) * 1e3
