"""Composable BFLC round pipeline (paper Fig. 1 as pluggable stages).

The paper's round is five distinct phases — sample, train,
committee-validate, aggregate-on-trigger, elect+reward — and the BFL
surveys (Wang & Hu 2021; Ma et al. 2020) taxonomize exactly these axes
(consensus, aggregation, incentive) as independently swappable.  This
module exposes the round that way:

* ``RoundContext`` threads one round's state (params, cohort, score
  table, packed records, chain, rng, per-stage timings) through the
  stages.
* Seven stage protocols — ``Sampler``, ``LocalTrainer``, ``Validator``,
  ``Packer``, ``Aggregator``, ``Elector``, ``Rewarder`` — each a plain
  callable ``(ctx) -> None`` with a string-keyed registry (the same
  idiom as ``repro.core.attacks.ATTACKS``).  Register a custom
  implementation with ``@register("aggregator", "my_impl")`` and name it
  when building a runtime; nothing inside this module needs editing.
* ``RoundPipeline`` drives the stages: sample/train/validate loop over
  cohorts until k qualified updates accumulate (the smart-contract
  trigger), then pack -> aggregate -> elect -> reward.  Every stage call
  is a ``bflc.<stage>`` span (``repro.tracing``) whose host seconds land
  in ``ctx.timings`` under its ``STAGE_TIMING_KEYS`` bucket; dotted keys
  (``train.batches``, ``validate.wait``, ``pack.chain``, ...) time the
  phases inside a bucket.

``BFLCRuntime`` is a thin facade over the default BFLC stage set;
``FLTrainer`` (Basic FL / CwMed) is the *same* pipeline with the
committee stages swapped for no-ops — baseline comparisons share one
code path.  The f32 (``pytree``) and fused-int8 (``fused_int8``)
aggregation engines are two registered ``Aggregator`` implementations;
the sharded multi-device engine (``local_sgd_sharded`` /
``top_k_int8_sharded`` / ``fused_int8_sharded``, in ``repro.fl.sharded``)
is exactly such a third set — registered stages, zero round-loop edits.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Protocol, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import election as election_mod
from repro.core.aggregation import (
    aggregate_pytrees,
    apply_update,
    flatten_updates,
)
from repro.core.attacks import ATTACKS
from repro.core.consensus import CommitteeConsensus, ValidationRecord
from repro.core.incentive import distribute_rewards
from repro.fl.client import sample_client_batches
from repro.tracing import span


def _unstack(tree, n: int):
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


# ``_unstack`` of a device stack as one compiled call: eagerly it
# dispatches an index, a slice and a squeeze per client and leaf
_split = jax.jit(_unstack, static_argnums=1)


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ----------------------------------------------------------------------
# round state
# ----------------------------------------------------------------------
@dataclass
class RoundContext:
    """State threaded through one round's stage pipeline.

    Built fresh per round by the runtime facade; every stage reads what
    it needs and writes its products back.  ``manager``/``chain`` are
    optional so the committee-free baselines run through the same
    pipeline.
    """

    # round inputs
    cfg: Any                               # BFLCConfig or FLConfig (duck-typed)
    rng: np.random.Generator
    adapter: Any
    data: Any                              # FederatedDataset
    params: Any                            # latest global model pytree
    round: int
    manager: Any = None                    # NodeManager (None for baselines)
    chain: Any = None                      # Chain (None for baselines)
    round_committee: List[int] = field(default_factory=list)  # frozen at round start
    committee: List[int] = field(default_factory=list)        # elector's output
    q_committee: int = 0
    p_trainers: int = 0
    # jitted helpers (built once by the runtime, shared across rounds)
    local_train_fn: Any = None
    score_matrix_fn: Any = None
    collusion: Any = None                  # CollusionPolicy
    malicious: Optional[Set[int]] = None   # baseline ground truth (no manager)
    # sharded round engine (populated when the runtime was built with a
    # mesh; see repro.fl.sharded for the stages that consume these)
    mesh: Any = None                       # 1-D ("data",) device mesh
    sharded_train_fn: Any = None           # shard_mapped local-SGD program
    sharded_quantize_fn: Any = None        # per-shard int8 stack codec
    sharded_agg_fn: Any = None             # D-sharded fused int8 reducer
    sharded_score_fn: Any = None           # P-sharded score-matrix program
    int8_score_fn: Any = None              # fused int8 scorer (single device)
    sharded_int8_score_fn: Any = None      # P-sharded fused int8 scorer
    # hierarchical (two-tier) round state — HierState, built per round by
    # the runtime when cfg.tiers > 1 (see repro.fl.hier)
    hier: Any = None
    # uploader -> (q, scales, row, d): the int8 validators' per-row
    # chain-codec quantization, cached so the packer reuses the rows
    # instead of re-quantizing the packed stack
    row_quant: Dict[int, Any] = field(default_factory=dict)
    # per-cohort state (overwritten each cohort; the async engine stages
    # these between its cohort ring slots and the shared context)
    cohort: int = 0
    trainers: List[int] = field(default_factory=list)
    cohort_updates: List[Any] = field(default_factory=list)
    cohort_stacked: Any = None             # trainer's update stack (rows >= P)
    cohort_poisoned: List[int] = field(default_factory=list)
    cohort_scores: Any = None              # validator's (P, Q) score matrix
    train_inflight: Any = None             # trainer's dispatched device stack
    # accumulated collection state
    trainers_total: List[int] = field(default_factory=list)
    updates: Dict[int, Any] = field(default_factory=dict)     # uploader -> update
    score_table: Dict[int, Dict[int, float]] = field(default_factory=dict)
    consensus: Optional[CommitteeConsensus] = None
    val_x: Any = None
    val_y: Any = None
    collected: bool = False                # k qualified updates reached
    # packed round output (Packer products)
    packed_ids: List[int] = field(default_factory=list)
    packed_scores: List[float] = field(default_factory=list)
    packed_updates: List[Any] = field(default_factory=list)
    packed_quantized: Any = None           # (q, scales, d, unravel) int8 stack
    weights: Any = None                    # aggregation weights (or None)
    # aggregation output
    aggregate: Any = None
    new_params: Any = None
    # incentive output
    rewards: Dict[int, float] = field(default_factory=dict)
    # per-stage wall-clock seconds (cumulative over cohorts)
    timings: Dict[str, float] = field(default_factory=dict)

    def is_malicious(self, node_id: int) -> bool:
        if self.manager is not None:
            return self.manager.nodes[node_id].is_malicious
        return self.malicious is not None and int(node_id) in self.malicious


# ----------------------------------------------------------------------
# stage protocols + registries
# ----------------------------------------------------------------------
class Stage(Protocol):
    def __call__(self, ctx: RoundContext) -> None: ...


class Sampler(Stage, Protocol):
    """Chooses ``ctx.trainers`` for the current cohort (empty = stop)."""


class LocalTrainer(Stage, Protocol):
    """Trains the cohort locally -> ``ctx.cohort_updates`` (may poison)."""


class Validator(Stage, Protocol):
    """Scores/admits the cohort's updates into ``ctx.updates`` and sets
    ``ctx.collected`` once the round's trigger condition is met.  May
    additionally define ``prepare(ctx)``, run once before cohort 0
    (e.g. to sample committee validation data)."""


class Packer(Stage, Protocol):
    """Selects the round's update set -> ``ctx.packed_*`` (+ chain update
    blocks, when a chain is present)."""


class Aggregator(Stage, Protocol):
    """Reduces the packed updates -> ``ctx.aggregate`` / ``ctx.new_params``
    (+ chain model block, when a chain is present)."""


class Elector(Stage, Protocol):
    """Seats the next committee -> ``ctx.committee``."""


class Rewarder(Stage, Protocol):
    """Distributes incentives and does end-of-round housekeeping."""


SAMPLERS: Dict[str, Sampler] = {}
LOCAL_TRAINERS: Dict[str, LocalTrainer] = {}
VALIDATORS: Dict[str, Validator] = {}
PACKERS: Dict[str, Packer] = {}
AGGREGATORS: Dict[str, Aggregator] = {}
ELECTORS: Dict[str, Elector] = {}
REWARDERS: Dict[str, Rewarder] = {}

REGISTRIES: Dict[str, Dict[str, Stage]] = {
    "sampler": SAMPLERS,
    "local_trainer": LOCAL_TRAINERS,
    "validator": VALIDATORS,
    "packer": PACKERS,
    "aggregator": AGGREGATORS,
    "elector": ELECTORS,
    "rewarder": REWARDERS,
}

STAGE_KINDS = tuple(REGISTRIES)

# the buckets under which both round runners record each stage's host
# seconds in ctx.timings; a dotted key ``<bucket>.<phase>`` (``stage_span``)
# times a phase inside its bucket, ``<bucket>.wait`` the host blocked on
# the device
STAGE_TIMING_KEYS = (
    "sample", "train", "validate", "pack", "aggregate", "elect", "reward",
)


def register(kind: str, name: str) -> Callable[[Stage], Stage]:
    """Decorator: ``@register("aggregator", "sharded")`` adds a stage to
    its registry.  Re-registering a name overwrites (last wins), so
    notebooks and tests can iterate."""
    if kind not in REGISTRIES:
        raise ValueError(f"unknown stage kind {kind!r} (want one of {STAGE_KINDS})")

    def deco(obj: Stage) -> Stage:
        REGISTRIES[kind][name] = obj
        return obj

    return deco


def resolve(kind: str, impl) -> Stage:
    """Name -> registered stage; callables pass through unchanged."""
    if callable(impl):
        return impl
    registry = REGISTRIES[kind]
    if impl not in registry:
        raise KeyError(
            f"no {kind} named {impl!r}; registered: {sorted(registry)}"
        )
    return registry[impl]


def stage_span(ctx: RoundContext, key: str):
    """The ``bflc.<key>`` span, its host seconds added to
    ``ctx.timings[key]``."""
    return span(f"bflc.{key}", ctx.timings, key)


def _sync_tree(ctx: RoundContext) -> list:
    """Every ctx field a stage may leave as in-flight device work.

    The sequential driver blocks on all of these after each stage so
    each stage's bucket holds its own compute: ``cohort_stacked`` /
    ``train_inflight`` catch the sharded trainer's async dispatch (which
    used to bleed into the validate bucket), ``cohort_scores`` the
    validator's score matrix, and a tiered round's ``sub_aggregates`` the
    per-slice fused reductions.  The async engine deliberately does NOT
    use this — it blocks only at true dependency edges."""
    sync = [ctx.cohort_updates, ctx.cohort_stacked, ctx.train_inflight,
            ctx.cohort_scores, ctx.packed_quantized, ctx.aggregate,
            ctx.new_params]
    if ctx.hier is not None:
        sync.append(ctx.hier.sub_aggregates)
    return sync


# ----------------------------------------------------------------------
# pipeline driver
# ----------------------------------------------------------------------
@dataclass
class RoundPipeline:
    """Ordered stage set for one round.

    ``run`` loops sample -> train -> validate over cohorts until the
    validator sets ``ctx.collected`` (k qualified updates — the paper's
    aggregation trigger) or ``max_cohorts`` is hit, then runs
    pack -> aggregate -> elect -> reward once.  Each stage call is timed
    into ``ctx.timings`` under its stage key, the wait for its device work
    under ``<key>.wait``."""

    sampler: Sampler
    local_trainer: LocalTrainer
    validator: Validator
    packer: Packer
    aggregator: Aggregator
    elector: Elector
    rewarder: Rewarder
    max_cohorts: int = 3

    def _timed(self, key: str, fn: Callable, ctx: RoundContext) -> None:
        with stage_span(ctx, key):
            fn(ctx)
            # jitted stages return asynchronously — block on the
            # jax-carrying ctx fields so each stage's compute lands in its
            # own bucket instead of bleeding into the next stage's first
            # sync point
            with stage_span(ctx, f"{key}.wait"):
                jax.block_until_ready(_sync_tree(ctx))

    def run(self, ctx: RoundContext) -> RoundContext:
        # stage -> timing key: STAGE_TIMING_KEYS
        prepare = getattr(self.validator, "prepare", None)
        if prepare is not None:
            self._timed("validate", prepare, ctx)
        for cohort in range(self.max_cohorts):
            ctx.cohort = cohort
            # rows quantized for an earlier cohort describe that cohort's
            # updates — an uploader re-drawn later trains a NEW update, so
            # a surviving cache entry would put a stale blob on the chain.
            # The final cohort's cache still reaches the packer (no clear
            # runs after ``collected``); a multi-cohort round's packer
            # falls back to a fresh (bitwise-identical) re-quantize.
            ctx.row_quant.clear()
            self._timed("sample", self.sampler, ctx)
            if not ctx.trainers:
                break
            self._timed("train", self.local_trainer, ctx)
            self._timed("validate", self.validator, ctx)
            if ctx.collected:
                break
        self._timed("pack", self.packer, ctx)
        self._timed("aggregate", self.aggregator, ctx)
        self._timed("elect", self.elector, ctx)
        self._timed("reward", self.rewarder, ctx)
        return ctx


def default_stage_names(cfg, mesh=None) -> Dict[str, str]:
    """The BFLC wiring for a config: quantize_chain flips the packer +
    aggregator pair to the fused-int8 engine; a mesh flips local training
    and committee validation (and, when quantized, the packer + aggregator)
    to the sharded multi-device engine (repro.fl.sharded).  The sharded
    validator scores f32 in every config — it reproduces the single-device
    score matrix bit-for-bit; the quantized-view scorers
    (``committee_int8`` / ``committee_int8_sharded``) are opt-in via
    ``stages=`` because int8 scoring noise moves median scores."""
    quantized = bool(getattr(cfg, "quantize_chain", False))
    sharded = mesh is not None
    names = {
        "sampler": "active",
        "local_trainer": "local_sgd_sharded" if sharded else "local_sgd",
        "validator": "committee_sharded" if sharded else "committee",
        "packer": "top_k_int8" if quantized else "top_k",
        "aggregator": "fused_int8" if quantized else "pytree",
        "elector": "by_candidates",
        "rewarder": "proportional",
    }
    if sharded and quantized:
        names["packer"] = "top_k_int8_sharded"
        names["aggregator"] = "fused_int8_sharded"
    return names


def baseline_stage_names(cfg, mesh=None) -> Dict[str, str]:
    """Basic FL / CwMed: the same pipeline with every committee stage a
    no-op — one central aggregation over an unvalidated cohort."""
    return {
        "sampler": "uniform",
        "local_trainer": "local_sgd_sharded" if mesh is not None
        else "local_sgd",
        "validator": "accept_all",
        "packer": "all",
        "aggregator": "pytree",
        "elector": "none",
        "rewarder": "none",
    }


def build_pipeline(
    names: Dict[str, str],
    overrides: Optional[Dict[str, Any]] = None,
    max_cohorts: int = 3,
) -> RoundPipeline:
    """Stage names (+ optional per-kind overrides: a registered name or a
    bare callable) -> RoundPipeline."""
    import repro.fl.sharded  # noqa: F401  (registers the sharded stage set)

    merged = dict(names)
    if overrides:
        unknown = set(overrides) - set(STAGE_KINDS)
        if unknown:
            raise ValueError(
                f"unknown stage kinds {sorted(unknown)} (want {STAGE_KINDS})"
            )
        merged.update(overrides)
    return RoundPipeline(
        **{kind: resolve(kind, merged[kind]) for kind in STAGE_KINDS},
        max_cohorts=max_cohorts,
    )


# ----------------------------------------------------------------------
# default BFLC stages (paper Fig. 1)
# ----------------------------------------------------------------------
@register("sampler", "active")
def sample_active(ctx: RoundContext) -> None:
    """(1) k%-active sampling, committee excluded, topped up from the
    full membership when the draw comes in short (shape stability)."""
    cfg, rng = ctx.cfg, ctx.rng
    active = ctx.manager.sample_active(rng, cfg.active_proportion)
    trainers = [
        i for i in active
        if i not in ctx.round_committee and i not in ctx.updates
    ][: ctx.p_trainers]
    if len(trainers) < ctx.p_trainers:
        extra = [
            i for i in ctx.manager.active_ids()
            if i not in ctx.round_committee and i not in ctx.updates
            and i not in trainers
        ]
        need = min(ctx.p_trainers - len(trainers), len(extra))
        if need > 0:
            trainers += rng.choice(extra, size=need, replace=False).tolist()
    ctx.trainers = trainers


@register("sampler", "uniform")
def sample_uniform(ctx: RoundContext) -> None:
    """Baseline sampling: uniform draw over all clients, no committee to
    exclude; single cohort (a second call yields no new trainers)."""
    cfg, rng = ctx.cfg, ctx.rng
    if ctx.updates:
        ctx.trainers = []
        return
    n = ctx.data.num_clients
    m = max(2, int(round(n * cfg.active_proportion)))
    ctx.trainers = rng.choice(n, m, replace=False).tolist()


def sample_cohort_batches(ctx: RoundContext):
    """The cohort's stacked local batches: (P, steps, b, ...), (P, steps, b).

    One rng draw per trainer in ``ctx.trainers`` order — the single- and
    multi-device trainers share this so a fixed seed produces the same
    stream (the differential tests compare chain hashes)."""
    cfg, rng = ctx.cfg, ctx.rng
    with stage_span(ctx, "train.batches"):
        pairs = [
            sample_client_batches(
                rng, ctx.data.client_images[i], ctx.data.client_labels[i],
                cfg.local_steps, cfg.local_batch,
            )
            for i in ctx.trainers
        ]
        return (np.stack([p[0] for p in pairs]),
                np.stack([p[1] for p in pairs]))


def poison_cohort_updates(ctx: RoundContext, updates: List[Any]) -> List[int]:
    """Per-node attack injection for malicious trainers (in place).

    Returns the poisoned indices (also recorded in ``ctx.cohort_poisoned``)
    so the scorers know whether the trainer's update stack still matches
    the update list (``cohort_stack``)."""
    cfg, rng = ctx.cfg, ctx.rng
    attack = ATTACKS[cfg.attack]
    poisoned = []
    for idx, node_id in enumerate(ctx.trainers):
        if ctx.is_malicious(node_id):
            updates[idx] = attack(
                rng, updates[idx], cfg.attack_sigma, ref=ctx.params
            ) if cfg.attack == "gaussian" else attack(rng, updates[idx])
            poisoned.append(idx)
    ctx.cohort_poisoned = poisoned
    return poisoned


class LocalSGDTrainer:
    """(2) cohort-batched local SGD (one vmapped XLA program) + per-node
    attack injection for malicious trainers.

    Split into ``dispatch`` (host rng batch draws + async XLA launch into
    ``ctx.train_inflight``, the stack also left on ``ctx.cohort_stacked``
    for the scorer) and ``finalize`` (per-client split + attack injection)
    so the async engine can overlap cohort t+1's device compute with
    cohort t's host-side validate/pack work; ``__call__`` runs both
    back-to-back — the sequential engine is unchanged, op for op."""

    def dispatch(self, ctx: RoundContext) -> None:
        xs, ys = sample_cohort_batches(ctx)
        with stage_span(ctx, "train.dispatch"):
            ctx.train_inflight = ctx.local_train_fn(ctx.params, xs, ys)
        ctx.cohort_stacked = ctx.train_inflight

    def finalize(self, ctx: RoundContext) -> None:
        stacked = ctx.train_inflight
        ctx.train_inflight = None
        with stage_span(ctx, "train.unstack"):
            updates = _split(stacked, len(ctx.trainers))
            poison_cohort_updates(ctx, updates)
        ctx.cohort_updates = updates

    def __call__(self, ctx: RoundContext) -> None:
        self.dispatch(ctx)
        self.finalize(ctx)


train_local_sgd = register("local_trainer", "local_sgd")(LocalSGDTrainer())


def cohort_stack(ctx: RoundContext):
    """The cohort's update stack for a score program: the trainer's own
    while no update was poisoned (it then holds the list's values bit for
    bit), else ``ctx.cohort_updates`` re-stacked in a ``validate.restack``
    span."""
    if ctx.cohort_stacked is not None and not ctx.cohort_poisoned:
        return ctx.cohort_stacked
    with stage_span(ctx, "validate.restack"):
        return _stack(ctx.cohort_updates)


class CommitteeValidator:
    """(3) committee scoring: the P x Q accuracy matrix in one batched
    call, collusion overlay, median acceptance via CommitteeConsensus.

    ``prepare`` runs once per round: samples each member's validation
    batch and binds the (live) score table to the consensus object.
    ``_scores_device`` is the engine hook — subclasses swap in the
    sharded / fused-int8 score programs (repro.fl.sharded) without
    touching the consensus bookkeeping below.  ``dispatch`` launches the
    score program asynchronously (device result parked in
    ``ctx.cohort_scores``, no host rng consumed); ``finalize`` gathers it
    and runs the collusion overlay + consensus admissions; ``__call__``
    runs both back-to-back, so the sequential engine is unchanged."""

    # dispatch consumes no host rng (pure device launch) — the async
    # engine's rng-edge chaining reads this
    dispatch_uses_rng = False

    def prepare(self, ctx: RoundContext) -> None:
        cfg, rng = ctx.cfg, ctx.rng
        with stage_span(ctx, "validate.batches"):
            vpairs = [
                sample_client_batches(
                    rng, ctx.data.client_images[j], ctx.data.client_labels[j],
                    1, cfg.val_batch,
                )
                for j in ctx.round_committee
            ]
            ctx.val_x = np.stack([p[0][0] for p in vpairs])
            ctx.val_y = np.stack([p[1][0] for p in vpairs])
        ctx.consensus = CommitteeConsensus(
            ctx.round_committee, accept_threshold=cfg.accept_threshold
        )
        ctx.consensus.bind_score_table(ctx.score_table)

    def _scores_device(self, ctx: RoundContext):
        """The (rows >= P, Q) accuracy matrix of this cohort's candidates,
        as the score program's (possibly still in-flight) device result."""
        return ctx.score_matrix_fn(
            ctx.params, cohort_stack(ctx), ctx.val_x, ctx.val_y
        )

    def dispatch(self, ctx: RoundContext) -> None:
        ctx.cohort_scores = self._scores_device(ctx)

    def finalize(self, ctx: RoundContext) -> None:
        cfg, rng = ctx.cfg, ctx.rng
        # gather + drop padding rows (sharded scorers return >= P rows)
        with stage_span(ctx, "validate.wait"):
            scores = np.asarray(ctx.cohort_scores)
        honest_scores = scores[: len(ctx.cohort_updates)]
        ctx.cohort_scores = honest_scores               # (P, Q)
        for i, uploader in enumerate(ctx.trainers):
            row = {}
            for j, member in enumerate(ctx.round_committee):
                s = float(honest_scores[i, j])
                if cfg.collusion:
                    s = ctx.collusion.score(
                        rng,
                        ctx.manager.nodes[member].is_malicious,
                        ctx.manager.nodes[uploader].is_malicious,
                        s,
                    )
                row[member] = s
            ctx.score_table[uploader] = row
        for idx, uploader in enumerate(ctx.trainers):
            ctx.consensus.validate(uploader, uploader)
            ctx.updates[uploader] = ctx.cohort_updates[idx]
        ctx.trainers_total += ctx.trainers
        # the paper's aggregation trigger: k QUALIFIED updates.  Packing
        # unqualified updates just to reach k would force one poisoned
        # update per round whenever honest trainers < k.
        if len(ctx.consensus.accepted_records()) >= cfg.k_updates:
            ctx.collected = True

    def __call__(self, ctx: RoundContext) -> None:
        self.dispatch(ctx)
        self.finalize(ctx)


register("validator", "committee")(CommitteeValidator())


def cache_row_quant(ctx: RoundContext, q, s, d: int) -> None:
    """Record the cohort's per-row chain-codec quantization on the context.

    ``q``/``s`` are the int8 scorer's (rows, Dpad) / (rows, nblk) arrays —
    the rows the committee just scored ARE the blobs a quantizing packer
    would store (identical tiling), so the packer stacks the cached rows
    instead of re-quantizing the packed updates.  Entries hold (array,
    array, row, d) references; the k packed rows are sliced at pack time."""
    for i, uploader in enumerate(ctx.trainers):
        ctx.row_quant[uploader] = (q, s, i, d)


def cached_row_stack(ctx: RoundContext, ids: Optional[List[int]] = None):
    """(q, s, d) stacked from the row-quant cache for the given uploaders
    (default: the packed set), or None when any row is missing (e.g. the
    default f32 validator ran — nothing was quantized yet, so there is
    nothing to reuse)."""
    ids = ctx.packed_ids if ids is None else ids
    cache = ctx.row_quant
    if not cache or any(u not in cache for u in ids):
        return None
    entries = [cache[u] for u in ids]
    q = jnp.stack([e[0][e[2]] for e in entries])
    s = jnp.stack([e[1][e[2]] for e in entries])
    return q, s, entries[0][3]


class Int8CommitteeValidator(CommitteeValidator):
    """Committee scoring straight from the chain-codec int8 view of each
    update (opt-in: ``stages={"validator": "committee_int8"}``): the fused
    Pallas pass rebuilds every candidate from its quantized row in one
    read, so the committee scores exactly the blob a quantizing packer
    would store.  Scores differ from the f32 validator by quantization
    noise only (tolerance-bounded in tests), so it is not the default —
    the default stays bit-compatible with the f32 oracle."""

    def _scores_device(self, ctx: RoundContext):
        if ctx.int8_score_fn is None:
            raise RuntimeError(
                "committee_int8 needs ctx.int8_score_fn — build the runtime "
                "with quantize_chain=True (the fused scorer shares the "
                "chain codec's unravel structure)"
            )
        stack, _ = flatten_updates(ctx.cohort_updates)
        scores, q, s = ctx.int8_score_fn(
            ctx.params, stack, ctx.val_x, ctx.val_y
        )
        cache_row_quant(ctx, q, s, int(stack.shape[1]))
        return scores


register("validator", "committee_int8")(Int8CommitteeValidator())


@register("validator", "accept_all")
def validate_accept_all(ctx: RoundContext) -> None:
    """Committee-free admission (Basic FL / CwMed): every update enters
    the round set unscored; one cohort satisfies the trigger."""
    for idx, uploader in enumerate(ctx.trainers):
        ctx.updates[int(uploader)] = ctx.cohort_updates[idx]
    ctx.trainers_total += [int(t) for t in ctx.trainers]
    ctx.collected = True


def _select_top_k(ctx: RoundContext) -> List[ValidationRecord]:
    """(3b) top-k qualified records; if the community could not produce k
    qualified updates (extreme malicious fractions), the best qualified
    one fills the remaining slots so the chain layout invariant holds
    (logged via duplicate uploader ids)."""
    cfg = ctx.cfg
    if ctx.consensus is None:
        raise RuntimeError(
            "top-k packers select from committee validation records — pair "
            "them with a consensus-producing validator (e.g. 'committee'), "
            "or swap in a score-free packer (e.g. 'all')"
        )
    records = sorted(
        ctx.consensus.accepted_records(), key=lambda r: -r.median_score
    )[: cfg.k_updates]
    if not records:  # nothing qualified: fall back to best available
        records = sorted(
            ctx.consensus.records, key=lambda r: -r.median_score
        )[:1]
    while len(records) < cfg.k_updates:
        records.append(records[0])
    return records


def _set_packed(ctx: RoundContext, records: List[ValidationRecord]) -> None:
    ctx.packed_ids = [r.uploader for r in records]
    ctx.packed_scores = [r.median_score for r in records]
    ctx.packed_updates = [ctx.updates[u] for u in ctx.packed_ids]
    ctx.weights = ctx.packed_scores if ctx.cfg.weight_by_score else None


@register("packer", "top_k")
def pack_top_k(ctx: RoundContext) -> None:
    """Packs the top-k qualified updates as f32 update blocks."""
    _set_packed(ctx, _select_top_k(ctx))
    with stage_span(ctx, "pack.chain"):
        for i, (u, sc) in enumerate(zip(ctx.packed_ids, ctx.packed_scores)):
            ctx.chain.append_update(ctx.packed_updates[i], u, sc)
            ctx.manager.nodes[u].score_history.append(sc)


@register("packer", "top_k_int8")
def pack_top_k_int8(ctx: RoundContext) -> None:
    """Quantized chain packing (paper §IV.D): flatten the packed cohort
    once, quantize the whole (K, D) stack in one kernel launch, store
    int8 blobs as update blocks, and hand the quantized stack to the
    fused aggregator — the f32 stack never hits HBM.  When an int8
    validator already quantized the round's rows, the cached rows are
    stacked instead (identical tiling — nothing is re-quantized)."""
    from repro.kernels.ops import quantize_stack

    _set_packed(ctx, _select_top_k(ctx))
    cached = cached_row_stack(ctx)
    if cached is not None:
        q, s, d = cached
        unravel = ctx.chain.codec.unravel
    else:
        stack, unravel = flatten_updates(ctx.packed_updates)
        q, s, d = quantize_stack(stack)
    with stage_span(ctx, "pack.chain"):
        for i, (u, sc) in enumerate(zip(ctx.packed_ids, ctx.packed_scores)):
            ctx.chain.append_update(
                {"q": q[i], "scales": s[i], "d": d}, u, sc, encoded=True
            )
            ctx.manager.nodes[u].score_history.append(sc)
    ctx.packed_quantized = (q, s, d, unravel)


@register("packer", "all")
def pack_all(ctx: RoundContext) -> None:
    """Baseline packing: every collected update, optionally size-weighted
    (classic FedAvg weighting); no chain, no scores."""
    cfg = ctx.cfg
    ctx.packed_ids = list(ctx.updates)
    ctx.packed_updates = [ctx.updates[u] for u in ctx.packed_ids]
    ctx.packed_scores = []
    weights = None
    if getattr(cfg, "size_weighted", False) and cfg.aggregation == "fedavg":
        weights = [len(ctx.data.client_labels[i]) for i in ctx.packed_ids]
    ctx.weights = weights


def _commit_aggregate(ctx: RoundContext, agg) -> None:
    ctx.aggregate = agg
    ctx.new_params = apply_update(ctx.params, agg)
    if ctx.chain is not None:
        with stage_span(ctx, "aggregate.chain"):
            ctx.chain.append_model(ctx.new_params, ctx.round + 1)


@register("aggregator", "pytree")
def aggregate_dense(ctx: RoundContext) -> None:
    """(4) dense aggregation over f32 update pytrees (jnp einsum/median,
    or the per-method Pallas kernels when cfg.use_kernels)."""
    cfg = ctx.cfg
    agg = aggregate_pytrees(
        ctx.packed_updates, method=cfg.aggregation, weights=ctx.weights,
        trim=getattr(cfg, "trim", 1),
        use_kernels=getattr(cfg, "use_kernels", False),
    )
    _commit_aggregate(ctx, agg)


@register("aggregator", "fused_int8")
def aggregate_fused_int8(ctx: RoundContext) -> None:
    """(4) fused one-pass aggregation straight from the chain's int8
    representation (one int8 read of the stack, dequant in-register)."""
    from repro.kernels.ops import aggregate_quantized

    cfg = ctx.cfg
    if ctx.packed_quantized is None:
        raise RuntimeError(
            "fused_int8 aggregator needs a quantizing packer (e.g. "
            "'top_k_int8') to stage the int8 stack in ctx.packed_quantized"
        )
    q, s, d, unravel = ctx.packed_quantized
    agg = unravel(aggregate_quantized(
        q, s, d, method=cfg.aggregation,
        weights=None if ctx.weights is None else jnp.asarray(ctx.weights),
        trim=cfg.trim,
    ))
    _commit_aggregate(ctx, agg)


def fill_committee(manager, committee: List[int], q_committee: int) -> List[int]:
    """Keep committee size exactly q_committee (shape stability).

    Backfill prefers nodes with the best score history (the managers'
    view of reputation) — random backfill re-opens the §IV.C induction
    to takeover whenever a round packs fewer candidates than q."""
    pool = [i for i in manager.active_ids() if i not in committee]
    pool.sort(key=lambda i: -manager.nodes[i].latest_score)
    committee = list(committee)
    while len(committee) < q_committee and pool:
        committee.append(pool.pop(0))
    return sorted(committee[:q_committee])


@register("elector", "by_candidates")
def elect_by_candidates(ctx: RoundContext) -> None:
    """(5) next committee from this round's validated providers (§IV.B);
    falls back to the sitting committee when no candidates packed."""
    cfg = ctx.cfg
    cand = dict(zip(ctx.packed_ids, ctx.packed_scores))
    elected = election_mod.elect(
        cfg.election_method, ctx.rng, cand, ctx.q_committee
    ) or list(ctx.round_committee)
    ctx.committee = fill_committee(ctx.manager, elected, ctx.q_committee)


@register("elector", "none")
def elect_none(ctx: RoundContext) -> None:
    """No election (baselines / static-committee ablations)."""


@register("rewarder", "proportional")
def reward_proportional(ctx: RoundContext) -> None:
    """(5) profit sharing by contribution (§IV.A) + end-of-round
    housekeeping: blacklist kicks and chain pruning."""
    cfg = ctx.cfg
    cand = dict(zip(ctx.packed_ids, ctx.packed_scores))
    ctx.rewards = distribute_rewards(ctx.manager, cand, cfg.reward_pool)
    if cfg.kick_below >= 0 and ctx.consensus is not None:
        for r in ctx.consensus.records:
            if r.median_score < cfg.kick_below:
                ctx.manager.kick(r.uploader)
    if cfg.prune_keep_rounds > 0:
        ctx.chain.prune(cfg.prune_keep_rounds)


@register("rewarder", "none")
def reward_none(ctx: RoundContext) -> None:
    """No incentive layer (baselines)."""
