"""The chain (``core/blockchain.py``): the ``pack.chain`` and
``aggregate.chain`` spans of ``ctx.timings`` per round, the packer's
update blocks and the model block appended (codec encode, payload digest
with its fetches from the device, SHA-256).  None where the program keeps
no such span."""
import numpy as np

KEYS = ("pack.chain", "aggregate.chain")


def read(rec):
    if rec["kind"] != "round" or not any(k in t for t in rec["timings"]
                                         for k in KEYS):
        return None
    return float(np.mean([sum(t.get(k, 0.0) for k in KEYS)
                          for t in rec["timings"]])) * 1e3
