"""``kv_read_share.serve`` on the CPU: over a tiny serving run, plain and
hybrid, the reader gives the engine's own ``ServeReport.kv_read_share``
(the hybrid's attention layers read every row: ``ragged_block`` keeps a
model with recurrent layers on dense attention); against a program without
the count it reads nothing."""
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
import tiny_hybrid  # noqa: E402
from bench import harness  # noqa: E402

SEED = 2 ** 31 + 45


def reader(root=REPO):
    return harness.load_module("metrics", "kv_read_share.serve", root)


def served(tmp_path, make_root, cell):
    """A tiny run's root, its record and the engine's report."""
    reports = []

    def keep_report(eng):
        inner = eng.run

        def run(*args, **kwargs):
            reports.append(inner(*args, **kwargs))
            return reports[-1]

        eng.run = run

    root = make_root(tmp_path)
    res, out = harness.run_cell(cell, seed=SEED, seconds=1.0, trace=False,
                                devices=jax.devices()[:1], t0=0.0,
                                root=root, patch=keep_report)
    assert res["correct"], out["checks"]
    return root, out["record"], reports[-1]


@pytest.mark.parametrize("make_root,cell,ragged", [
    (tiny.make_root, tiny.SERVE_CELL, True),
    (tiny_hybrid.make_root, tiny_hybrid.HYBRID_CELL, False),
], ids=["plain", "hybrid"])
def test_reader_gives_the_engine_count(tmp_path, make_root, cell, ragged):
    root, rec, report = served(tmp_path, make_root, cell)
    if ragged:
        assert 0.0 < report.kv_read_share < 1.0
    else:
        assert report.kv_read_share == 1.0
    assert reader(root).read(rec) == pytest.approx(
        100.0 * report.kv_read_share, rel=1e-12)


def test_reader_reads_nothing_without_the_count(monkeypatch):
    """The record of a program whose engine has no ``kv_rows_read`` (its
    tick reads every row), and a record of another kind."""
    import repro.serve.engine as engine

    rec = {"kind": "serve", "ticks": 10, "chips": 1, "max_len": 48,
           "num_slots": 4, "requests": [{"prompt_len": 8, "tokens": 11}],
           "config": {}}
    monkeypatch.delattr(engine, "kv_rows_read")
    assert reader().read(rec) is None
    assert reader().read({"kind": "round"}) is None
