"""95th percentile over the window's requests of the time per output token
after the first: (finished - first token) / (tokens - 1)."""
import numpy as np


def read(rec):
    if rec["kind"] != "serve":
        return None
    t = [(r["finished"] - r["first_token"]) / (r["tokens"] - 1)
         for r in rec["requests"] if r["tokens"] > 1 and r["finished"] >= 0]
    return float(np.percentile(t, 95)) * 1e3 if t else None
