"""The round cells' check on the CPU, at a tiny size: a sound run is
correct; the lower-precision control and each planted fault are not.

The cells are added to a copy of the benchmark by files alone
(``tiny.make_root``); the harness's look for a chip is skipped and the rest
of a run is driven as ``bench/run.py`` drives it."""
from __future__ import annotations

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

import tiny  # noqa: E402
from bench import harness  # noqa: E402

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def run(root, cell=tiny.ROUND_CELL, patch=None, chips=1):
    return harness.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                            devices=jax.devices()[:chips], t0=0.0,
                            root=root, patch=patch)


def test_sound_run_is_correct(root):
    res, out = run(root)
    assert res["correct"], out["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert res["device"]["count"] == 1


def _inner(rt, kind):
    return getattr(rt.pipeline, kind)


def state_unchanged(rt):
    from repro.fl.pipeline import _commit_aggregate

    stage = _inner(rt, "aggregator")
    stage.inner = lambda ctx: _commit_aggregate(
        ctx, jax.tree.map(jnp.zeros_like, ctx.params))


def half_batch(rt):
    stage = _inner(rt, "packer")
    inner = stage.inner

    def pack(ctx):
        inner(ctx)
        k = len(ctx.weights)
        ctx.weights = list(ctx.weights[: k // 2]) + [0.0] * (k - k // 2)

    stage.inner = pack


def answer_altered(rt):
    stage = _inner(rt, "local_trainer")
    inner = stage.inner

    def train(ctx):
        inner(ctx)
        ctx.cohort_updates[0] = jax.tree.map(lambda x: -x,
                                             ctx.cohort_updates[0])

    stage.inner = train


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, answer_altered],
                         ids=lambda f: f.__name__)
def test_planted_fault_is_not_correct(root, fault):
    res, out = run(root, patch=fault)
    assert not res["correct"], out["numbers"]


def test_exchange_left_out_is_not_correct(root):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    res, out = run(root, tiny.ROUND4_CELL, chips=4)
    assert res["correct"], out["checks"]

    def first_chip_only(rt):
        inner = rt._sharded_train

        def train(params, xs, ys):
            out = inner(params, xs, ys)
            share = xs.shape[0] // 4
            return jax.tree.map(lambda x: x.at[share:].set(0.0), out)

        rt._sharded_train = train

    res, out = run(root, tiny.ROUND4_CELL, patch=first_chip_only, chips=4)
    assert not res["correct"], out["numbers"]


def test_control_is_not_correct(root):
    res, out = run(root)
    limits = out["checks"]
    calibrate = _calibrate(root)
    # the CPU computes float32 products exactly whatever the precision
    # flag, so the control here is also put in bfloat16
    control = calibrate.round_readings(out, 1, jnp.bfloat16)["control"]
    assert any(control[k] > limits[k]["limit"] for k in control
               if k in limits), control


def _calibrate(root):
    import importlib.util

    path = os.path.join(root, "bench", "calibrate.py")
    spec = importlib.util.spec_from_file_location("bench_calibrate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
