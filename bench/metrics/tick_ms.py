"""The decode tick (``serve/engine.py``, ``make_decode_step``): device
time per tick program call in the traced window."""


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or not tr:
        return None
    secs, calls = tr["modules"].get("jit_tick", (0.0, 0))
    return secs / calls * 1e3 if calls else None
