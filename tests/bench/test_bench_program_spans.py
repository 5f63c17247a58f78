"""The per-layer metrics that read the program's own spans: on the tiny
round cell (CPU) each yields a finite, non-negative number, and on a
record without those spans each reads nothing instead of raising."""
from __future__ import annotations

import math
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

SEED = 2 ** 31 + 13
SPAN_METRICS = ("train_batches_ms", "train_dispatch_ms", "chain_append_ms",
                "device_wait_ms.round")


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    res, out = harness.run_cell(tiny.ROUND_CELL, seed=SEED, seconds=0.5,
                                trace=False, devices=jax.devices()[:1],
                                t0=0.0, root=root)
    assert res["correct"], out["checks"]
    return out["record"]


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_metric_reads_tiny_round(record, metric):
    value = harness.load_module("metrics", metric, REPO).read(record)
    assert value is not None and math.isfinite(value) and value >= 0
    train = harness.load_module("metrics", "stage_train_ms", REPO).read(record)
    if metric.startswith("train_"):
        assert value <= train + 1e-6


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_metric_silent_without_spans(record, metric):
    buckets = [{k: v for k, v in t.items() if "." not in k}
               for t in record["timings"]]
    bare = dict(record, timings=buckets)
    assert harness.load_module("metrics", metric, REPO).read(bare) is None
