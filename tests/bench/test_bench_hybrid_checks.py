"""The hybrid serving cells' check on the CPU, at a tiny size (two periods
of Mamba, Mamba, attention, Mamba; prompts of 37 and 100 tokens, neither a
multiple of the scan's chunk): a sound run is correct; the lower-precision
control, a zeroed SSM state on insert and scan pad steps that advance the
state are not."""
from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import tiny_hybrid  # noqa: E402
from bench import harness  # noqa: E402

SEED = 2 ** 31 + 33


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_hybrid.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def cal(root):
    path = os.path.join(root, "bench", "calibrate_hybrid.py")
    spec = importlib.util.spec_from_file_location("bench_calibrate_hybrid",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(root, patch=None):
    return harness.run_cell(tiny_hybrid.HYBRID_CELL, seed=SEED, seconds=1.0,
                            trace=False, devices=jax.devices()[:1], t0=0.0,
                            root=root, patch=patch)


@pytest.fixture(scope="module")
def sound(root):
    return run(root)


def test_sound_run_is_correct(sound):
    res, out = sound
    assert res["correct"], out["checks"]
    assert res["failed"] == 0 and res["attempted"] == 10
    assert set(res["metrics"]) == {"tok_s", "ttft_p95_ms", "tpot_p95_ms",
                                   "setup_s"}
    notes = out["notes"]
    assert notes["served tokens checked"] > 0 and notes["states checked"] >= 4
    assert notes["slots checked"] == len(out["held"]) > 0
    # five prompts of 37 tokens and five of 100, none a multiple of 64
    assert notes["prompt tokens"] == 5 * 37 + 5 * 100


def test_state_zeroed_on_insert_is_not_correct(root, cal):
    res, out = run(root, patch=cal.state_zeroed)
    assert not res["correct"]
    assert out["numbers"]["state_diff"] > out["checks"]["state_diff"]["limit"]


def test_pad_steps_that_advance_the_state_are_not_correct(root, cal):
    with cal.pad_steps_run():
        res, out = run(root)
    assert not res["correct"]
    assert out["numbers"]["state_diff"] > out["checks"]["state_diff"]["limit"]


def test_control_is_not_correct(sound, cal):
    _, out = sound
    # the CPU computes float32 products exactly whatever the precision
    # flag, so the control here is also put in bfloat16
    control = cal.control_readings(out, jnp.bfloat16)
    limits = out["checks"]
    assert not harness.checks_pass(
        {k: {"value": v, "limit": limits[k]["limit"]}
         for k, v in control.items()}), control
