"""The selective scan (``models/mamba.py``, under the ``mamba.scan`` named
scope): device time of the scan's operations per prefill program call at
the window's mean prompt length.  The scan time of the traced calls is
taken per prompt token of those same calls (the window's first admissions,
``bench/scope_trace.py``; the operations holding the scan state's shape
stand in for the scope, which a v5e trace does not keep), times the mean
prompt of every prefill in the window; on a trace that kept the whole
window that is the scan time per call.  Nothing where the run kept no such
reading."""
from bench.scope_trace import prefill_prompts


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or not tr or "scope" not in tr:
        return None
    scope = tr["scope"]
    prompts = prefill_prompts(rec)
    tokens = sum(prompts[: scope["calls"]])
    if not tokens or scope["scope_s"] <= 0:
        return None
    return scope["scope_s"] / tokens * (sum(prompts) / len(prompts)) * 1e3
