"""Parity + failure-edge suite for the async pipelined round engine.

The async engine (``repro.fl.async_engine``) replaces the round
*schedule*, not the stages: every test here runs the SAME community/seed
through ``schedule="sequential"`` and ``schedule="async"`` and demands
bit-identical products — chain fingerprints (block hashes, packed
uploader ids, scores), ``RoundLog``s, and aggregated params — across

* the flat f32 engine, malicious (rng-serialized regime) and clean
  (overlapped regime) — the rng-edge chaining must hold in both;
* the sharded fused-int8 engine on 1/2/8 forced CPU devices;
* the hierarchical two-tier engine (slice pipelining), int8+mesh and f32;
* the committee-free FLTrainer baselines.

Failure edges: a stage raising mid-ring must abort the round with the
chain untouched (no torn layout — all appends live in the tail), and
``max_cohorts`` exhaustion must drain the ring cleanly and still match
the sequential engine bit for bit.

The row_quant staleness regression (rows cached for an earlier cohort
leaking onto the chain as stale blobs when an uploader is re-drawn) is
pinned here too: it fails on the engine without the cohort-boundary
``ctx.row_quant.clear()``.
"""
import jax
import numpy as np
import pytest

from repro.api import build_runtime
from repro.core.blockchain import UPDATE
from repro.data import make_femnist_like
from repro.fl import femnist_adapter
from repro.fl.async_engine import AsyncRoundPipeline, SLOT_FIELDS
from repro.fl.pipeline import (
    STAGE_TIMING_KEYS,
    CommitteeValidator,
    RoundContext,
    _sync_tree,
    cache_row_quant,
    pack_top_k_int8,
    resolve,
)

DEVICE_COUNTS = (1, 2, 8)

CFG = dict(active_proportion=0.5, committee_fraction=0.3, k_updates=4,
           local_steps=3, local_batch=8, malicious_fraction=0.25,
           attack_sigma=1.5, seed=0)

# small/fast variant for the failure-edge tests
FAST = dict(CFG, local_steps=2)


@pytest.fixture(scope="module")
def ds():
    return make_femnist_like(num_clients=24, mean_samples=40,
                             test_size=200, seed=3)


@pytest.fixture(scope="module")
def adapter():
    return femnist_adapter(width=8)


def _chain_fingerprint(chain):
    return (
        chain.height,
        [b.hash for b in chain.blocks],
        [b.uploader for b in chain.blocks if b.kind == UPDATE],
        [b.score for b in chain.blocks if b.kind == UPDATE],
    )


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _run_pair(adapter, ds, cfg, rounds=2, **kw):
    """The same config through both schedules -> (sequential, async)."""
    rt_seq = build_runtime(adapter, ds, dict(cfg), **kw)
    rt_async = build_runtime(adapter, ds, dict(cfg), schedule="async", **kw)
    logs_seq = rt_seq.run(rounds, eval_every=rounds)
    logs_async = rt_async.run(rounds, eval_every=rounds)
    return rt_seq, rt_async, logs_seq, logs_async


def _assert_parity(rt_seq, rt_async, logs_seq, logs_async,
                   hashes_equal=True):
    if hashes_equal:
        assert _chain_fingerprint(rt_seq.chain) == \
            _chain_fingerprint(rt_async.chain)
    assert logs_seq == logs_async
    assert rt_seq.committee == rt_async.committee
    assert rt_seq.chain.verify() and rt_async.chain.verify()
    _leaves_equal(rt_seq.global_params(), rt_async.global_params())


# ----------------------------------------------------------------------
# wiring
# ----------------------------------------------------------------------
def test_schedule_arg_validation(ds, adapter):
    with pytest.raises(ValueError, match="schedule"):
        build_runtime(adapter, ds, dict(CFG), schedule="overlapped")
    with pytest.raises(ValueError, match="schedule"):
        build_runtime(adapter, ds, {"seed": 0}, baseline=True,
                      schedule="overlapped")


def test_async_wraps_same_stage_set(ds, adapter):
    rt_seq = build_runtime(adapter, ds, dict(CFG))
    rt_async = build_runtime(adapter, ds, dict(CFG), schedule="async")
    assert isinstance(rt_async.pipeline, AsyncRoundPipeline)
    assert rt_async.schedule == "async"
    # same registered stage objects, different runner
    for kind in ("sampler", "local_trainer", "validator", "packer",
                 "aggregator", "elector", "rewarder"):
        assert getattr(rt_async.pipeline, kind) is \
            getattr(rt_seq.pipeline, kind)
    assert rt_async.pipeline.max_cohorts == rt_seq.pipeline.max_cohorts


def test_sync_tree_covers_inflight_fields():
    """The sequential driver's blanket sync must see every ctx field a
    stage can leave as in-flight device work — in particular the split
    stages' ``train_inflight`` / ``cohort_stacked`` / ``cohort_scores``
    (whose device time used to bleed into the next stage's bucket)."""
    sentinels = {f: object() for f in
                 ("cohort_updates", "cohort_stacked", "train_inflight",
                  "cohort_scores", "packed_quantized", "aggregate",
                  "new_params")}
    ctx = RoundContext(cfg=None, rng=np.random.default_rng(0),
                       adapter=None, data=None, params=None, round=0)
    for f, v in sentinels.items():
        setattr(ctx, f, v)
    synced = _sync_tree(ctx)
    for f, v in sentinels.items():
        assert any(s is v for s in synced), f"_sync_tree misses ctx.{f}"

    class _H:
        sub_aggregates = object()

    ctx.hier = _H()
    assert any(s is _H.sub_aggregates for s in _sync_tree(ctx))


def test_async_timing_schema(ds, adapter):
    """Async rounds keep the stage timing schema: exactly the stage
    buckets as undotted keys, every dotted (phase) key under one of them,
    train/validate buckets actually accumulate."""
    rt = build_runtime(adapter, ds, dict(FAST), schedule="async")
    rt.run_round()
    timings = rt.stage_timings[0]
    assert {k for k in timings if "." not in k} == set(STAGE_TIMING_KEYS)
    for k in timings:
        assert k.split(".")[0] in STAGE_TIMING_KEYS, k
    assert timings["train"] > 0 and timings["validate"] > 0


# ----------------------------------------------------------------------
# failure edges
# ----------------------------------------------------------------------
class _Boom(Exception):
    pass


class _RaisingValidator:
    """Delegates to the committee validator; forces a second cohort and
    raises mid-ring (cohort 1's validate, with cohort work in flight)."""

    def __init__(self):
        self.inner = resolve("validator", "committee")
        self.cohorts_seen = []

    def prepare(self, ctx):
        self.inner.prepare(ctx)

    def __call__(self, ctx):
        self.cohorts_seen.append(ctx.cohort)
        if ctx.cohort >= 1:
            raise _Boom("mid-ring failure")
        self.inner(ctx)
        ctx.collected = False      # force the ring past cohort 0


@pytest.mark.parametrize("schedule", ("sequential", "async"))
def test_midring_failure_leaves_chain_untouched(ds, adapter, schedule):
    """A stage raising with a later cohort already in flight must not
    commit anything: every chain append lives in the tail, so the round
    aborts with the chain exactly as it started (no torn layout)."""
    val = _RaisingValidator()
    rt = build_runtime(adapter, ds, dict(FAST), stages={"validator": val},
                       schedule=schedule)
    h0 = rt.chain.height
    blocks0 = [b.hash for b in rt.chain.blocks]
    with pytest.raises(_Boom):
        rt.run_round()
    assert val.cohorts_seen == [0, 1]  # the failure really was mid-ring
    assert rt.chain.height == h0
    assert [b.hash for b in rt.chain.blocks] == blocks0
    assert rt.chain.verify()
    assert rt.logs == []               # no partial round log either


class _NeverCollect:
    """Committee validator that never fires the trigger: the ring runs
    to max_cohorts exhaustion and must drain cleanly."""

    def __init__(self):
        self.inner = resolve("validator", "committee")

    def prepare(self, ctx):
        self.inner.prepare(ctx)

    def __call__(self, ctx):
        self.inner(ctx)
        ctx.collected = False


def test_max_cohorts_exhaustion_drains_ring(ds, adapter):
    """collected never fires -> the engine runs all max_cohorts cohorts,
    drains the ring, runs the tail exactly once, and stays bit-identical
    to the sequential engine."""
    rt_seq = build_runtime(adapter, ds, dict(FAST),
                           stages={"validator": _NeverCollect()})
    rt_async = build_runtime(adapter, ds, dict(FAST),
                             stages={"validator": _NeverCollect()},
                             schedule="async")
    log_seq = rt_seq.run_round()
    log_async = rt_async.run_round()
    assert log_seq == log_async
    # all three cohorts ran: trainers accumulated past one cohort's worth
    assert log_async.trainers > rt_async.p_trainers
    assert _chain_fingerprint(rt_seq.chain) == \
        _chain_fingerprint(rt_async.chain)
    assert rt_async.chain.verify()
    # exactly one tail: k update blocks + one model block on top of genesis
    assert rt_async.chain.height == 1 + FAST["k_updates"] + 1


# ----------------------------------------------------------------------
# row_quant staleness regression (bugfix pin)
# ----------------------------------------------------------------------
class _StaleCacheValidator(CommitteeValidator):
    """Cohort 0: int8-scores the cohort (caching its per-row blobs) but
    admits nothing — forcing a second cohort that re-draws the same
    uploaders with NEW updates.  Without the engine's cohort-boundary
    ``ctx.row_quant.clear()`` the packer then reuses cohort 0's cached
    rows for cohort 1's packed updates: a stale blob on the chain."""

    def __call__(self, ctx):
        if ctx.cohort == 0:
            from repro.core.aggregation import flatten_updates

            stack, _ = flatten_updates(ctx.cohort_updates)
            _, q, s = ctx.int8_score_fn(
                ctx.params, stack, ctx.val_x, ctx.val_y
            )
            cache_row_quant(ctx, q, s, int(stack.shape[1]))
            ctx.trainers_total += list(ctx.trainers)
            return
        super().__call__(ctx)


def test_row_quant_cleared_between_cohorts(ds, adapter):
    """Regression: the packed chain blobs must quantize the updates that
    were actually packed — never rows cached for an earlier cohort's
    updates.  Fails on the engine without the cohort-boundary clear."""
    from repro.core.aggregation import flatten_updates
    from repro.kernels.ops import quantize_stack

    captured = {}

    def spy_packer(ctx):
        pack_top_k_int8(ctx)
        captured["q"] = np.asarray(ctx.packed_quantized[0])
        captured["s"] = np.asarray(ctx.packed_quantized[1])
        captured["updates"] = [jax.tree.map(np.asarray, u)
                               for u in ctx.packed_updates]

    cfg = dict(active_proportion=1.0, committee_fraction=0.3, k_updates=4,
               local_steps=2, local_batch=8, quantize_chain=True,
               use_kernels=True, seed=0)
    rt = build_runtime(adapter, ds, cfg,
                       stages={"validator": _StaleCacheValidator(),
                               "packer": spy_packer})
    rt.run_round()

    stack, _ = flatten_updates(captured["updates"])
    q_fresh, s_fresh, _ = quantize_stack(stack)
    np.testing.assert_array_equal(captured["q"], np.asarray(q_fresh))
    np.testing.assert_array_equal(captured["s"], np.asarray(s_fresh))


# ----------------------------------------------------------------------
# full parity: sequential vs async, bit for bit
# ----------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("malicious", (True, False),
                         ids=("malicious", "clean"))
def test_async_flat_f32_parity(ds, adapter, malicious):
    """Flat f32 rounds: with malicious trainers the rng edges serialize
    the graph (the regime where a reordered draw would flip the chain);
    clean rounds overlap train/validate — both must be bit-identical."""
    cfg = dict(CFG) if malicious else dict(CFG, malicious_fraction=0.0)
    _assert_parity(*_run_pair(adapter, ds, cfg))


@pytest.mark.slow
@pytest.mark.parametrize("ndev", DEVICE_COUNTS)
def test_async_sharded_int8_parity(round_mesh, ds, adapter, ndev):
    """Sharded fused-int8 rounds on 1/2/8 devices: the async schedule
    overlaps cohort t+1's shard_mapped training with cohort t's
    committee work and must reproduce every chain bit."""
    mesh = round_mesh(ndev)
    cfg = dict(CFG, quantize_chain=True, use_kernels=True)
    _assert_parity(*_run_pair(adapter, ds, cfg, mesh=mesh))


@pytest.mark.slow
@pytest.mark.parametrize("quantized", (True, False), ids=("int8", "f32"))
def test_async_tiered_parity(round_mesh, ds, adapter, quantized):
    """Hierarchical two-tier rounds: the prefetch_safe tiered sampler
    lets slice s+1 train while slice s sub-aggregates — the headline
    overlap — and the chain must still match the sequential engine."""
    if quantized:
        cfg = dict(CFG, active_proportion=1.0, quantize_chain=True,
                   use_kernels=True, tiers=2)
        kw = {"mesh": round_mesh(2)}
    else:
        cfg = dict(CFG, active_proportion=1.0, malicious_fraction=0.0,
                   tiers=2)
        kw = {}
    rt_seq, rt_async, logs_seq, logs_async = _run_pair(
        adapter, ds, cfg, **kw
    )
    _assert_parity(rt_seq, rt_async, logs_seq, logs_async)
    assert rt_seq.hier_logs == rt_async.hier_logs


@pytest.mark.slow
def test_async_baseline_parity(ds, adapter):
    """FLTrainer (committee-free) under the async schedule: same params,
    same accuracies."""
    cfg = dict(active_proportion=0.5, local_steps=2, local_batch=8,
               malicious_fraction=0.25, seed=0)
    bl_seq = build_runtime(adapter, ds, dict(cfg), baseline=True)
    bl_async = build_runtime(adapter, ds, dict(cfg), baseline=True,
                             schedule="async")
    bl_seq.run(2)
    bl_async.run(2)
    assert bl_seq.accuracies == bl_async.accuracies
    _leaves_equal(bl_seq.params, bl_async.params)


def test_slot_fields_match_context():
    """Every ring-slot field must exist on RoundContext (the executor
    stages them attribute-by-attribute)."""
    ctx = RoundContext(cfg=None, rng=np.random.default_rng(0),
                       adapter=None, data=None, params=None, round=0)
    for f in SLOT_FIELDS:
        assert hasattr(ctx, f)
