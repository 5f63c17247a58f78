"""The hybrid serving cell's benchmark code on the CPU: the Jamba2-3B
reference's operation and byte counts against hand counts, the scan's
scope reduction, the two readers of the prefill trace, and the reduction
of a trace that ran out before its window closed."""
from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import harness, scope_trace  # noqa: E402


def test_jamba_counts_against_hand_count():
    jamba = harness.load_module("configs", "jamba2-3b", REPO)
    c = harness.load_json("configs", "jamba2-3b", REPO)
    assert jamba.kinds(c).index("attn") == 7 and jamba.layer_counts(c) == (2, 26)
    D, din, F = 2560, 5120, 8192
    mlp = 3 * D * F
    assert jamba.matmul_params(c, "attn") == 2 * D * D + 2 * D * 128 + mlp
    assert jamba.matmul_params(c, "mamba") == (D * 2 * din + din * 192
                                               + 160 * din + din * D + mlp)
    # 3,029,337,472 parameters in float32, and 16 slots' state both ways
    assert jamba.params(c) == 3_029_337_472
    state = 4 * 26 * (3 * din + din * 16)
    assert jamba.state_bytes_per_slot(c) == state
    assert jamba.weight_bytes(c) == 4 * 3_029_337_472 + 2 * 16 * state
    assert jamba.kv_bytes_per_position(c) == 4 * 2 * 2 * 128
    scan = 2 * 4 * din + 7 * din * 16 + 4 * din
    per_attn = 2 * jamba.matmul_params(c, "attn")
    per_mamba = 2 * jamba.matmul_params(c, "mamba") + scan
    assert jamba.decode_flops(c, 1) == (2 * (per_attn + 4 * 20 * 128)
                                        + 26 * per_mamba + 2 * D * 65536)
    # 3 tokens: 6 causal pairs
    assert jamba.prefill_flops(c, 3) == (2 * (3 * per_attn + 4 * 20 * 128 * 6)
                                         + 26 * 3 * per_mamba + 2 * D * 65536)


def test_scope_reduction_synthetic():
    calls = [("jit_prefill_tok(1)", 0, 100), ("jit_tick(2)", 100, 150),
             ("jit_prefill_tok(1)", 200, 260), ("jit_prefill_tok(1)", 900, 990)]
    ops = [("%while.1 = (f32[1,5120,16]) while()", 10, 60),
           ("%fusion.2 = f32[1,5120,16] fusion()", 20, 30),
           ("%fusion.3 = f32[8] fusion()", 60, 90),
           ("%fusion.4 = f32[16,5120,16] fusion()", 110, 140),
           ("%fusion.5 = f32[1,5120,16] fusion()", 120, 130),
           ("%while.6 = (f32[1,5120,16]) while()", 210, 250),
           ("%while.7 = (f32[1,5120,16]) while()", 910, 950)]
    r = scope_trace.scope_reduce(calls, ops, (0, 500), "jit_prefill_tok",
                                 "[1,5120,16]")
    # two calls in the window; [10,60] holds the nested op; [210,250]; the
    # tick's op and the call after the window do not count
    assert r == {"scope_s": pytest.approx(90e-9), "calls": 2}


def test_scan_and_prefill_per_token_readers():
    scan = harness.load_module("metrics", "scan_ms", REPO)
    per_token = harness.load_module("metrics", "prefill_us_per_token", REPO)
    # prefilled in order of admission: 4000, 480, 1000; one never admitted
    reqs = [{"prompt_len": 480, "admitted": 2.0},
            {"prompt_len": 1000, "admitted": 3.0},
            {"prompt_len": 4000, "admitted": 1.0},
            {"prompt_len": 2000, "admitted": -1.0}]
    rec = {"kind": "serve", "requests": reqs,
           "trace": {"modules": {"jit_prefill_tok": [2.7, 3]},
                     "scope": {"scope_s": 0.5, "calls": 3}}}
    # a whole trace: every call over every prompt token; the scan per call
    assert per_token.read(rec) == pytest.approx(2.7 / 5480 * 1e6)
    assert scan.read(rec) == pytest.approx(0.5 / 3 * 1e3)
    # a trace that kept the first two calls (4000 and 480 tokens, unequal):
    # their device time over their own tokens, not the window's mean prompt
    rec["trace"] = {"modules": {"jit_prefill_tok": [2.0, 2]},
                    "scope": {"scope_s": 0.4, "calls": 2}}
    assert per_token.read(rec) == pytest.approx(2.0 / 4480 * 1e6)
    # the scan per token of the kept calls, at the window's mean prompt
    assert scan.read(rec) == pytest.approx(0.4 / 4480 * (5480 / 3) * 1e3)
    del rec["trace"]["scope"]
    assert scan.read(rec) is None
    assert per_token.read(dict(rec, trace=None)) is None


def test_scope_tracer_reduces_over_the_part_the_trace_kept():
    s = 1e9
    ops = [("%fusion.1 = f32[8] fusion()", 1 * s, 3 * s),
           ("%fusion.2 = f32[8] fusion()", 4 * s, 10 * s)]
    # the device's last operation ends 40 s before the window closes
    assert scope_trace.kept_window(ops, (0, 50 * s)) == ((0, 10 * s), True)
    # a window that closes right after its last operation is whole
    assert scope_trace.kept_window(ops, (0, 10.2 * s)) == ((0, 10.2 * s),
                                                           False)
