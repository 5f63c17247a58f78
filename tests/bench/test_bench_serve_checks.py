"""The serving cells' check on the CPU, at a tiny size: a sound run is
correct; the lower-precision control, an altered token and a slot whose
cache is never written are not."""
from __future__ import annotations

import os
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import tiny  # noqa: E402
from bench import harness  # noqa: E402

SEED = 2 ** 31 + 21


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def run(root, patch=None):
    return harness.run_cell(tiny.SERVE_CELL, seed=SEED, seconds=1.0,
                            trace=False, devices=jax.devices()[:1], t0=0.0,
                            root=root, patch=patch)


def test_sound_run_is_correct(root):
    res, out = run(root)
    assert res["correct"], out["checks"]
    assert res["failed"] == 0 and res["attempted"] == 12
    assert set(res["metrics"]) == {"tok_s", "ttft_p95_ms", "tpot_p95_ms",
                                   "setup_s"}
    assert out["notes"]["served tokens checked"] > 0


def token_altered(eng):
    inner = eng._tick

    def tick(params, tokens, positions, cache):
        tok, pos, cache = inner(params, tokens, positions, cache)
        return (tok + 1) % eng.cfg.vocab_size, pos, cache

    eng._tick = tick


def cache_never_written(eng):
    def insert(cache, tokens, positions, slot_cache, first_tok, pos0, b):
        tokens = jax.lax.dynamic_update_slice(tokens, first_tok, (b, 0))
        positions = jax.lax.dynamic_update_slice(positions, pos0[None], (b,))
        return tokens, positions, cache

    eng._insert = jax.jit(insert)


@pytest.mark.parametrize("fault", [token_altered, cache_never_written],
                         ids=lambda f: f.__name__)
def test_planted_fault_is_not_correct(root, fault):
    res, out = run(root, patch=fault)
    assert not res["correct"], out["numbers"]


def test_control_is_not_correct(root):
    import importlib.util

    res, out = run(root)
    path = os.path.join(root, "bench", "calibrate.py")
    spec = importlib.util.spec_from_file_location("bench_calibrate", path)
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    # the CPU computes float32 products exactly whatever the precision
    # flag, so the control here is also put in bfloat16
    import jax.numpy as jnp

    control = cal.serve_readings(out, jnp.bfloat16)["control"]
    limits = out["checks"]
    assert any(control[k] > limits[k]["limit"] for k in control), control
