"""Prefill (``launch/steps.make_prefill_step``): device time of the prefill
program calls in the traced window over the prompt tokens of those same
calls, which are the window's first admissions (``bench/scope_trace.py``):
a trace that ran out before the window closed kept only its first calls."""
from bench.scope_trace import prefill_prompts


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or not tr:
        return None
    secs, calls = tr["modules"].get("jit_prefill_tok", (0.0, 0))
    tokens = sum(prefill_prompts(rec)[: int(round(calls))])
    if not calls or not tokens:
        return None
    return secs / tokens * 1e6
