"""95th percentile over the window's requests of the time from each
request's due arrival to the delivery of its first token."""
import numpy as np


def read(rec):
    if rec["kind"] != "serve":
        return None
    t = [r["first_token"] - r["arrival"] for r in rec["requests"]
         if r["first_token"] >= 0]
    return float(np.percentile(t, 95)) * 1e3 if t else None
