"""Prefill (``launch/steps.make_prefill_step``): device time per prefill
program call in the traced window."""


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or not tr:
        return None
    secs, calls = tr["modules"].get("jit_prefill_tok", (0.0, 0))
    return secs / calls * 1e3 if calls else None
