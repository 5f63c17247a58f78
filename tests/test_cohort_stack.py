"""The cohort's update stack: the trainer splits it per client in one
compiled call and hands it on whole; the scorers score it in place unless
an update was poisoned, and re-stack the list (``validate.restack``) only
then."""
import jax
import numpy as np
import pytest

from repro.api import build_runtime
from repro.core.blockchain import UPDATE
from repro.data import make_femnist_like
from repro.fl import femnist_adapter
from repro.fl import pipeline as pl

CFG = dict(active_proportion=0.5, committee_fraction=0.3, k_updates=4,
           local_steps=2, local_batch=8, seed=0)


@pytest.fixture(scope="module")
def ds():
    return make_femnist_like(num_clients=24, mean_samples=40,
                             test_size=200, seed=3)


@pytest.fixture(scope="module")
def adapter():
    return femnist_adapter(width=8)


def _trees_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _fingerprint(chain):
    return (chain.height, [b.hash for b in chain.blocks],
            [b.uploader for b in chain.blocks if b.kind == UPDATE])


class _RecordingTrainer(pl.LocalSGDTrainer):
    def __init__(self):
        self.seen = []

    def finalize(self, ctx):
        stacked = ctx.train_inflight
        super().finalize(ctx)
        self.seen.append((stacked, ctx.cohort_stacked, ctx.cohort_updates))


class _BothScores(pl.CommitteeValidator):
    """Scores the trainer's stack, and beside it the re-stacked list."""

    def __init__(self):
        self.seen = []

    def _scores_device(self, ctx):
        out = super()._scores_device(ctx)
        restacked = ctx.score_matrix_fn(
            ctx.params, pl._stack(ctx.cohort_updates), ctx.val_x, ctx.val_y)
        self.seen.append((ctx.cohort_stacked is not None, np.asarray(out),
                          np.asarray(restacked)))
        return out


class _WithheldStack(pl.LocalSGDTrainer):
    def dispatch(self, ctx):
        super().dispatch(ctx)
        ctx.cohort_stacked = None


def test_split_and_scores_match_eager(ds, adapter):
    trainer, scorer = _RecordingTrainer(), _BothScores()
    rt = build_runtime(adapter, ds, dict(CFG),
                       stages={"local_trainer": trainer, "validator": scorer})
    rt.run_round()
    assert trainer.seen and scorer.seen
    for stacked, published, updates in trainer.seen:
        assert published is stacked
        assert isinstance(updates, list)
        eager = pl._unstack(stacked, len(updates))
        assert len(eager) == len(updates)
        for a, b in zip(updates, eager):
            assert _trees_equal(a, b)
    for used_stack, out, restacked in scorer.seen:
        assert used_stack
        assert np.array_equal(out, restacked)
    assert "validate.restack" not in rt.stage_timings[0]


def test_chain_same_with_stack_withheld(ds, adapter):
    rt = build_runtime(adapter, ds, dict(CFG))
    rt_w = build_runtime(adapter, ds, dict(CFG),
                         stages={"local_trainer": _WithheldStack()})
    logs, logs_w = rt.run(2, eval_every=2), rt_w.run(2, eval_every=2)
    assert _fingerprint(rt.chain) == _fingerprint(rt_w.chain)
    assert logs == logs_w
    assert rt.chain.verify()
    assert all("validate.restack" not in t for t in rt.stage_timings)
    assert all("validate.restack" in t for t in rt_w.stage_timings)


def _score_spy(name: str, field: str):
    """The registered scorer ``name`` with its score program (``ctx.<field>``)
    wrapped to record the stack it is given."""

    class Spy(type(pl.resolve("validator", name))):
        def __init__(self):
            self.seen = []

        def _scores_device(self, ctx):
            program = getattr(ctx, field)

            def record(params, stacked, vx, vy):
                self.seen.append(dict(
                    stacked=stacked, stale=ctx.cohort_stacked,
                    updates=list(ctx.cohort_updates),
                    poisoned=list(ctx.cohort_poisoned)))
                return program(params, stacked, vx, vy)

            setattr(ctx, field, record)
            try:
                return super()._scores_device(ctx)
            finally:
                setattr(ctx, field, program)

    return Spy()


@pytest.mark.parametrize("name,field,ndev", [
    ("committee", "score_matrix_fn", None),
    ("committee_sharded", "sharded_score_fn", 2),
])
def test_poisoned_cohort_scores_the_list(round_mesh, ds, adapter, name,
                                         field, ndev):
    mesh = None if ndev is None else round_mesh(ndev)
    spy = _score_spy(name, field)
    rt = build_runtime(adapter, ds, dict(CFG, malicious_fraction=0.25),
                       mesh=mesh, stages={"validator": spy})
    rt.run_round()
    assert any(s["poisoned"] for s in spy.seen)
    for s in spy.seen:
        n = len(s["updates"])
        scored = jax.tree.map(lambda x: np.asarray(x)[:n], s["stacked"])
        assert _trees_equal(scored, pl._stack(s["updates"]))
        stale = jax.tree.map(lambda x: np.asarray(x)[:n], s["stale"])
        for i in s["poisoned"]:
            row = lambda t: jax.tree.map(lambda x: x[i], t)  # noqa: E731
            assert not _trees_equal(row(scored), row(stale))
    assert "validate.restack" in rt.stage_timings[0]

    clean = _score_spy(name, field)
    rt = build_runtime(adapter, ds, dict(CFG), mesh=mesh,
                       stages={"validator": clean})
    rt.run_round()
    assert clean.seen
    assert all(s["stacked"] is s["stale"] for s in clean.seen)
    assert "validate.restack" not in rt.stage_timings[0]
