"""Asynchronous pipelined round engine (ROADMAP item 1).

The sequential driver (``repro.fl.pipeline.RoundPipeline``) runs
sample -> train -> validate strictly in cohort order and blocks on every
stage's device work before starting the next (``_timed``'s blanket sync).
Train is ~90% of a sharded round, and everything the committee does after
a cohort trains — gathering the score matrix, consensus bookkeeping,
sub-aggregation, chain hashing — is host-side work during which the mesh
sits idle.  This module replaces the *schedule*, not the stages: the same
registered Sampler/LocalTrainer/Validator/... stage set is executed as a
dependency graph whose nodes are the stages' dispatch/finalize halves, so
cohort t+1's local-SGD program is already in flight on the mesh while the
host finishes cohort t's committee work.

Design
------
* **Cohort ring.**  Per-cohort context fields (``SLOT_FIELDS``) live in
  ``CohortSlot``s and are staged slot <-> ctx around every node, so two
  cohorts can be in flight without clobbering each other.  The ring is
  two deep: starting cohort t+1 requires cohort t-1 fully finalized
  (edge ``sample[t+1] <- validate_finalize[t-1]``), bounding in-flight
  update stacks to two — a tiered round keeps its streaming-ingest
  memory bound at two slices instead of one.
* **Dependency graph.**  Each cohort contributes sample ->
  train_dispatch -> train_finalize -> validate_dispatch ->
  validate_finalize nodes (stages without a dispatch/finalize split run
  as one atomic node — a serialization point, never an error).
  validate_dispatch t reads trainer t's ``cohort_stacked``; validator
  nodes are serialized across cohorts (the consensus trigger and the
  sampler's ``i not in ctx.updates`` exclusion read their products); the
  tail pack -> aggregate -> elect -> reward runs once after the last
  finalize, so **chain append is ordered** exactly as in the sequential
  engine.  (The elector -> next round's committee exclusion edge is the
  runtime's round loop boundary — rounds never overlap, since round t+1
  trains from round t's model block.)
* **rng edges.**  Bit-identical parity with the sequential engine
  requires the host ``np.random.Generator`` stream to be consumed in the
  sequential order.  Every node that may draw host rng (sampling, batch
  draws, attack injection when the cohort holds malicious trainers,
  collusion overlay when the scoring committee holds malicious members,
  a hier slice's inner prepare) is chained along "rng edges" in creation
  order = sequential order.  With no malicious nodes the chain is
  sample -> train_dispatch -> validate_dispatch -> ... which still
  permits full train/validate overlap; with malicious nodes the chain
  runs through the finalize nodes and the graph degrades to the
  sequential order — which is exactly when the parity tests demand
  bit-identical chain hashes, and they get them in both regimes.
* **Sampler prefetch.**  A sampler advertising ``prefetch_safe = True``
  (the tiered sampler: partition frozen at cohort 0) lets cohort t+1 be
  sampled + train-dispatched while cohort t is still validating — the
  headline overlap (hier slice s+1 trains while slice s sub-aggregates).
  The flat samplers read the validator's admissions, so flat
  multi-cohort rounds serialize sample[t+1] behind validate_finalize[t]
  — the engine never speculates an rng draw it might have to undo.
* **Sync points.**  There is no blanket ``block_until_ready``: device
  work is awaited where a stage half genuinely consumes it
  (``train_finalize``'s host gather, ``validate_finalize``'s score
  gather, the tail's chain digests) plus one final sync in the reward
  node (``reward.wait``).  Each node runs inside a ``bflc.<bucket>``
  span whose host time is accumulated into ``ctx.timings`` under the
  same ``STAGE_TIMING_KEYS`` buckets as the sequential engine (dispatch
  time + whatever blocking its own sync point pays), with the same
  dotted phase keys; buckets are host-attributed — overlapped device
  time lands in whichever bucket blocked on it.
* **Failure.**  A node that raises aborts the run immediately: no tail
  node has run, so nothing was appended to the chain — a mid-ring
  failure cannot tear the chain layout (gated in tests), and in-flight
  device work for the next cohort is simply abandoned.

``BFLCRuntime``/``FLTrainer`` select this engine via
``build_runtime(..., schedule="async")``; ``AsyncRoundPipeline.run``
consumes and returns the same ``RoundContext`` and is bit-identical to
``RoundPipeline.run`` for every stage set shipped in this repo (parity
suite: tests/test_async_round.py).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.fl.pipeline import (
    RoundContext,
    RoundPipeline,
    STAGE_TIMING_KEYS,
    _sync_tree,
    stage_span,
)

# per-cohort RoundContext fields staged between ring slots and the shared
# context around every node
SLOT_FIELDS = (
    "cohort", "trainers", "cohort_updates", "cohort_stacked",
    "cohort_poisoned", "cohort_scores", "train_inflight", "row_quant",
)

RING_DEPTH = 2


@dataclass
class CohortSlot:
    """One ring slot: the per-cohort slice of RoundContext."""

    cohort: int
    trainers: List[int] = field(default_factory=list)
    cohort_updates: List[Any] = field(default_factory=list)
    cohort_stacked: Any = None
    cohort_poisoned: List[int] = field(default_factory=list)
    cohort_scores: Any = None
    train_inflight: Any = None
    row_quant: Dict[int, Any] = field(default_factory=dict)


@dataclass
class StageNode:
    """One schedulable unit: a stage (or stage half) bound to a cohort."""

    key: str                               # e.g. "train_dispatch[2]"
    kind: str                              # scheduler event class
    bucket: str                            # STAGE_TIMING_KEYS entry
    fn: Callable[[RoundContext], None]
    deps: List["StageNode"] = field(default_factory=list)
    slot: Optional[CohortSlot] = None
    cohort: Optional[int] = None
    rng: bool = False                      # consumes host rng
    priority: int = 1                      # 0 = dispatch-class (run first)
    order: int = 0                         # creation = sequential order
    done: bool = False
    skipped: bool = False


@dataclass
class AsyncRoundPipeline:
    """Drop-in replacement for ``RoundPipeline`` running the async
    schedule.  Same stage fields; ``run(ctx)`` mutates and returns the
    same ``RoundContext``."""

    sampler: Any
    local_trainer: Any
    validator: Any
    packer: Any
    aggregator: Any
    elector: Any
    rewarder: Any
    max_cohorts: int = 3

    @classmethod
    def from_pipeline(cls, p: RoundPipeline) -> "AsyncRoundPipeline":
        return cls(p.sampler, p.local_trainer, p.validator, p.packer,
                   p.aggregator, p.elector, p.rewarder, p.max_cohorts)

    def run(self, ctx: RoundContext) -> RoundContext:
        _AsyncRoundRun(self, ctx).run()
        return ctx


def _split(stage) -> bool:
    return hasattr(stage, "dispatch") and hasattr(stage, "finalize")


class _AsyncRoundRun:
    """One round's node graph + executor (grown cohort-by-cohort: a
    cohort's trainer/validator nodes and rng hazards depend on the
    sampled trainer list, so they are created when its sample runs)."""

    def __init__(self, pipe: AsyncRoundPipeline, ctx: RoundContext):
        self.pipe = pipe
        self.ctx = ctx
        self.nodes: List[StageNode] = []
        self.slots: Dict[int, CohortSlot] = {}
        self._order = 0
        self._rng_tail: Optional[StageNode] = None   # last rng-consuming node
        self._last_v: Optional[StageNode] = None     # validator serialization
        self._vf: Dict[int, StageNode] = {}          # cohort -> final V node
        self._samples: Dict[int, StageNode] = {}
        self._tail_made = False

    # ------------------------------------------------------------------
    # graph construction
    # ------------------------------------------------------------------
    def _add(self, key: str, kind: str, bucket: str, fn, *, deps=(),
             slot=None, cohort=None, rng=False, priority=1) -> StageNode:
        node = StageNode(key=key, kind=kind, bucket=bucket, fn=fn,
                         deps=[d for d in deps if d is not None],
                         slot=slot, cohort=cohort, rng=rng,
                         priority=priority, order=self._order)
        self._order += 1
        if rng:
            # chain host-rng consumers in creation (= sequential) order so
            # a fixed seed replays the sequential engine's exact stream
            if self._rng_tail is not None and self._rng_tail is not node:
                node.deps.append(self._rng_tail)
            self._rng_tail = node
        self.nodes.append(node)
        return node

    def _cohort_committee(self, c: int) -> List[int]:
        """The committee whose members score cohort c (collusion-rng
        hazard set): the slice sub-committee in a tiered round, the round
        committee otherwise."""
        hier = self.ctx.hier
        if hier is not None and hier.slices:
            return (hier.slices[c].committee
                    if c < len(hier.slices) else [])
        return self.ctx.round_committee

    def _add_sample(self, c: int) -> StageNode:
        sampler = self.pipe.sampler
        prefetch = bool(getattr(sampler, "prefetch_safe", False))
        rng = True
        if c > 0 and getattr(sampler, "rng_first_only", False):
            rng = False
        deps = []
        if c == 0:
            deps = [self._last_v]          # prepare node, when present
        elif prefetch:
            deps = [self._samples[c - 1], self._vf.get(c - RING_DEPTH)]
        else:
            # flat samplers read the validator's admissions (collected
            # trigger, `i not in ctx.updates` exclusion): no speculation
            deps = [self._vf[c - 1]]
        slot = CohortSlot(cohort=c)
        self.slots[c] = slot
        node = self._add(f"sample[{c}]", "sample", "sample",
                         self.pipe.sampler, deps=deps, slot=slot,
                         cohort=c, rng=rng, priority=0)
        self._samples[c] = node
        return node

    def _add_cohort_body(self, c: int) -> None:
        """Trainer + validator nodes for a sampled, non-empty cohort."""
        ctx, pipe = self.ctx, self.pipe
        slot = self.slots[c]
        cfg = ctx.cfg
        snode = self._samples[c]
        poisoned = any(ctx.is_malicious(i) for i in slot.trainers)
        collusion = bool(getattr(cfg, "collusion", False)) and any(
            ctx.is_malicious(m) for m in self._cohort_committee(c)
        )

        trainer, validator = pipe.local_trainer, pipe.validator
        if _split(trainer):
            td = self._add(f"train_dispatch[{c}]", "train", "train",
                           trainer.dispatch, deps=[snode], slot=slot,
                           cohort=c, rng=True, priority=0)
            tf = self._add(f"train_finalize[{c}]", "train", "train",
                           trainer.finalize, deps=[td], slot=slot,
                           cohort=c, rng=poisoned)
        else:
            tf = self._add(f"train[{c}]", "train", "train", trainer,
                           deps=[snode], slot=slot, cohort=c, rng=True)

        if _split(validator):
            vd = self._add(f"validate_dispatch[{c}]", "validate",
                           "validate", validator.dispatch,
                           deps=[tf, self._last_v], slot=slot, cohort=c,
                           rng=bool(getattr(validator, "dispatch_uses_rng",
                                            False)),
                           priority=0)
            vf = self._add(f"validate_finalize[{c}]", "validate_finalize",
                           "validate", validator.finalize, deps=[vd],
                           slot=slot, cohort=c, rng=collusion)
        else:
            # unknown monolithic validator: conservatively an rng consumer
            vf = self._add(f"validate[{c}]", "validate_finalize",
                           "validate", validator,
                           deps=[tf, self._last_v], slot=slot, cohort=c,
                           rng=True)
        self._vf[c] = vf
        self._last_v = vf

        if c + 1 < pipe.max_cohorts:
            self._add_sample(c + 1)

    def _make_tail(self, trigger: StageNode, slot: CohortSlot) -> None:
        """pack -> aggregate -> elect -> reward, serialized after the last
        cohort node — all chain appends happen here, in order."""
        if self._tail_made:
            return
        self._tail_made = True
        pipe = self.pipe
        dep = [trigger, self._last_v]

        def _reward_and_sync(ctx: RoundContext) -> None:
            pipe.rewarder(ctx)
            # the round's final sync point: nothing a caller observes
            # (new params, chain, logs) may still be in flight
            with stage_span(ctx, "reward.wait"):
                jax.block_until_ready(_sync_tree(ctx))

        for key, fn in (("pack", pipe.packer),
                        ("aggregate", pipe.aggregator),
                        ("elect", pipe.elector),
                        ("reward", _reward_and_sync)):
            node = self._add(key, "tail", key, fn, deps=dep, slot=slot,
                             rng=True)
            dep = [node]

    # ------------------------------------------------------------------
    # scheduler events
    # ------------------------------------------------------------------
    def _after_sample(self, node: StageNode) -> None:
        if self._tail_made:
            return
        if not node.slot.trainers:
            # empty cohort = the sequential loop's break
            self._make_tail(node, node.slot)
            return
        self._add_cohort_body(node.cohort)

    def _after_validate(self, node: StageNode) -> None:
        if self._tail_made:
            return
        c = node.cohort
        ctx = self.ctx
        if ctx.collected:
            nxt = self._samples.get(c + 1)
            if nxt is not None and not nxt.done:
                nxt.skipped = True
            live = [n for n in self.nodes
                    if n.cohort is not None and n.cohort > c
                    and (n.done or n.kind != "sample") and not n.skipped]
            if live:
                # a prefetch_safe sampler promised `collected` fires only
                # on the last cohort; it fired early with cohort c+1 work
                # (and its rng draws) already issued — refuse to continue
                # with a stream the sequential engine would not have drawn
                raise RuntimeError(
                    "async schedule: `collected` fired at cohort "
                    f"{c} with cohort {c + 1} already prefetched — the "
                    "sampler's prefetch_safe contract requires the "
                    "trigger to be shape-static (last cohort only)"
                )
            self._make_tail(node, node.slot)
        elif c + 1 >= self.pipe.max_cohorts:
            self._make_tail(node, node.slot)   # max_cohorts exhausted

    # ------------------------------------------------------------------
    # executor
    # ------------------------------------------------------------------
    def _pick(self) -> Optional[StageNode]:
        best = None
        best_k = None
        for n in self.nodes:
            if n.done or n.skipped:
                continue
            # a skipped dep (a cancelled prefetch sample) counts as
            # satisfied: it never ran, never will, and everything *it*
            # waited on was already done when it was skipped — its rng
            # successors (the tail) are free to proceed
            if any(not (d.done or d.skipped) for d in n.deps):
                continue
            k = (n.priority, n.order)
            if best is None or k < best_k:
                best, best_k = n, k
        return best

    def _exec(self, node: StageNode) -> None:
        ctx = self.ctx
        with stage_span(ctx, node.bucket):
            slot = node.slot
            if slot is not None:
                for f in SLOT_FIELDS:
                    setattr(ctx, f, getattr(slot, f))
            try:
                node.fn(ctx)
            finally:
                if slot is not None:
                    for f in SLOT_FIELDS:
                        setattr(slot, f, getattr(ctx, f))
        node.done = True
        if node.kind == "sample":
            self._after_sample(node)
        elif node.kind == "validate_finalize":
            self._after_validate(node)

    def run(self) -> None:
        ctx, pipe = self.ctx, self.pipe
        for key in STAGE_TIMING_KEYS:
            ctx.timings.setdefault(key, 0.0)
        prepare = getattr(pipe.validator, "prepare", None)
        if prepare is not None:
            self._last_v = self._add("prepare", "prepare", "validate",
                                     prepare, rng=True)
        if pipe.max_cohorts < 1:
            self._make_tail(self._last_v, CohortSlot(cohort=0))
        else:
            self._add_sample(0)
        while True:
            node = self._pick()
            if node is None:
                break
            self._exec(node)
        stuck = [n.key for n in self.nodes if not n.done and not n.skipped]
        if stuck:
            raise RuntimeError(
                f"async schedule deadlock: unrunnable nodes {stuck}"
            )
