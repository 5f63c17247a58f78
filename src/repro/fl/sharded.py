"""The sharded round engine: multi-device BFLC stages (ROADMAP follow-ups).

Three registered stages turn one round into a data-parallel program over a
1-D ``("data",)`` mesh (``repro.launch.mesh.make_round_mesh``), with zero
edits to the round loop:

* ``local_trainer = "local_sgd_sharded"`` — the P-client vmapped local-SGD
  program (``repro.fl.client``) shard_mapped over the mesh's data axis: P
  clients split across devices, each device scanning its client shard, the
  stacked update pytree all-gathered when the host unstacks it.  Batch
  sampling and attack injection are byte-identical to ``local_sgd`` (shared
  helpers), so a fixed seed yields the same rng stream — and the per-client
  math is the same XLA program, so f32 chain hashes match the single-device
  engine bit-for-bit.
* ``packer = "top_k_int8_sharded"`` — sharding-aware ``Packer``: the int8
  stack is built per-shard (each device quantizes its D-slice; tiles are
  BLOCK_D-aligned by construction so per-tile scales coincide with the
  single-device codec), blobs land on the chain in the same
  ``{"q", "scales", "d"}`` schema.
* ``aggregator = "fused_int8_sharded"`` — each device runs the fused
  int8->dequant->reduce kernel (PR 1) on its D-shard of the stack, then the
  model block is all-gathered (XLA inserts it at the first replicated use).
* ``validator = "committee_sharded"`` — the P x Q committee score matrix
  (paper §III.B, the consensus-side cost term of §V.A) shard_mapped over
  the mesh's data axis: each device scores its own P-shard of candidate
  rows against the replicated params + member val batches.  Updates arrive
  P-sharded straight from ``local_sgd_sharded`` (no intermediate
  all-gather when no row was poisoned); only the (P, Q) score matrix is
  gathered at the stage boundary, per the trainer's
  boundary-materialization rule.  Scores are bitwise identical to the
  single-device oracle — same per-candidate XLA program, just sharded.
* ``validator = "committee_int8_sharded"`` (opt-in) — same sharding, but
  each device flattens its P-shard of the trainer's device-resident update
  stack in-program, quantizes the rows with the chain codec and rebuilds
  candidates via the fused score-from-int8 Pallas pass
  (``repro.kernels.fused_score``): the committee scores exactly the blob a
  quantizing packer would store, within int8 tolerance of the f32 scores.
  The per-row (q, scales) are cached on the context so the packer reuses
  them instead of re-quantizing.

The stages read their pre-built programs from ``RoundContext``
(``sharded_train_fn`` / ``sharded_quantize_fn`` / ``sharded_agg_fn`` /
``sharded_score_fn`` / ``sharded_int8_score_fn``, built once per runtime
by ``BFLCRuntime(..., mesh=...)`` — see ``repro.api.build_runtime``).
Everything runs on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, which is how the
differential test harness (tests/test_sharded_round.py) exercises 1/2/8
devices without a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import flatten_updates, normalize_weights
from repro.fl.pipeline import (
    CommitteeValidator,
    LocalSGDTrainer,
    RoundContext,
    _select_top_k,
    _set_packed,
    _commit_aggregate,
    _unstack,
    cache_row_quant,
    cached_row_stack,
    cohort_stack,
    poison_cohort_updates,
    register,
    sample_cohort_batches,
    stage_span,
)


def _require(ctx: RoundContext, field: str, stage: str):
    fn = getattr(ctx, field)
    if fn is None:
        raise RuntimeError(
            f"{stage} needs ctx.{field} — build the runtime with a mesh "
            "(build_runtime(..., mesh=make_round_mesh(n)))"
        )
    return fn


def _pad_rows(tree, n: int, ndev: int):
    """Pad the leading (client) axis of a stacked pytree / array to a
    multiple of the mesh's data-axis size by repeating the last row.
    Per-row programs (local SGD, committee scoring) are independent, so
    padded rows never contaminate real clients and score rows are simply
    sliced off."""
    pad = (-n) % ndev
    if pad == 0:
        return tree
    return jax.tree.map(
        lambda x: np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])
        if isinstance(x, np.ndarray)
        else jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]),
        tree,
    )


def _pad_clients(xs: np.ndarray, ys: np.ndarray, ndev: int):
    """The trainer's batch padding: one `_pad_rows` over the (xs, ys) pair."""
    P = xs.shape[0]
    xs, ys = _pad_rows((xs, ys), P, ndev)
    return xs, ys, P


class ShardedLocalSGDTrainer(LocalSGDTrainer):
    """(2, sharded) cohort-batched local SGD, clients split over the mesh's
    data axis; one shard_mapped XLA program per cohort shape.  Same
    dispatch/finalize split as ``LocalSGDTrainer``: ``dispatch`` draws the
    batches and launches the shard_mapped program (result in flight on
    ``ctx.train_inflight``); ``finalize`` pays the host transfer and
    injects attacks."""

    def dispatch(self, ctx: RoundContext) -> None:
        train_fn = _require(ctx, "sharded_train_fn", "local_sgd_sharded")
        mesh = _require(ctx, "mesh", "local_sgd_sharded")
        ndev = dict(mesh.shape).get("data", mesh.devices.size)
        xs, ys = sample_cohort_batches(ctx)
        with stage_span(ctx, "train.dispatch"):
            xs, ys, _ = _pad_clients(xs, ys, ndev)
            stacked = train_fn(ctx.params, xs, ys)
        # the P-sharded update stack (padded rows included) stays on its
        # devices for the sharded validator — committee scoring consumes it
        # with zero relayout.
        ctx.cohort_stacked = stacked
        ctx.train_inflight = stacked

    def finalize(self, ctx: RoundContext) -> None:
        # the host copy is still needed: poisoning, per-uploader
        # bookkeeping (ctx.updates) and packing are host-side, and feeding
        # the later single-device stages a device-committed P-sharded
        # stack would make GSPMD replicate their compute per shard
        # (observed: pack/aggregate re-sharding pathology before this
        # gather).
        with stage_span(ctx, "train.wait"):
            host = jax.device_get(ctx.train_inflight)
        ctx.train_inflight = None
        with stage_span(ctx, "train.unstack"):
            updates = _unstack(host, len(ctx.trainers))  # padded rows dropped
            poison_cohort_updates(ctx, updates)
        ctx.cohort_updates = updates


train_local_sgd_sharded = register("local_trainer", "local_sgd_sharded")(
    ShardedLocalSGDTrainer()
)


def _pad_cached_to_shards(q, s, d: int, ndev: int):
    """Widen cached rows from the single-device width ``padded_dim(d)`` to
    the sharded width ``padded_dim_sharded(d, ndev)``.  The extra tiles
    are all-zero and the quantize kernel maps an all-zero tile to q=0 /
    scale=1.0, so appending exactly that is bitwise identical to
    quantizing the wider stack."""
    from repro.kernels.ops import padded_dim_sharded
    from repro.kernels.tiling import BLOCK_D

    pad = padded_dim_sharded(d, ndev) - q.shape[1]
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
        s = jnp.pad(s, ((0, 0), (0, pad // BLOCK_D)),
                    constant_values=1.0)
    return q, s


@register("packer", "top_k_int8_sharded")
def pack_top_k_int8_sharded(ctx: RoundContext) -> None:
    """Sharding-aware quantized packing: flatten the packed cohort once,
    quantize each device's D-shard of the (K, D) stack in parallel, store
    int8 blobs as update blocks, hand the (sharded) int8 stack to the
    sharded aggregator.  Rows already quantized by an int8 validator are
    reused from the row-quant cache (zero-padded to the shard boundary)
    instead of re-quantized."""
    quantize_fn = _require(ctx, "sharded_quantize_fn", "top_k_int8_sharded")
    mesh = _require(ctx, "mesh", "top_k_int8_sharded")
    ndev = dict(mesh.shape).get("data", mesh.devices.size)
    _set_packed(ctx, _select_top_k(ctx))
    cached = cached_row_stack(ctx)
    if cached is not None:
        q, s, d = cached
        q, s = _pad_cached_to_shards(q, s, d, ndev)
        unravel = ctx.chain.codec.unravel
    else:
        stack, unravel = flatten_updates(ctx.packed_updates)
        d = stack.shape[1]
        q, s = quantize_fn(stack)
    # one gather for the whole stack: slicing rows of the D-sharded arrays
    # inside the loop would pay a cross-device gather + host transfer per
    # blob (the digest reads the bytes anyway); the aggregator still gets
    # the sharded (q, s) below
    with stage_span(ctx, "pack.chain"):
        qh, sh = jax.device_get((q, s))
        for i, (u, sc) in enumerate(zip(ctx.packed_ids, ctx.packed_scores)):
            ctx.chain.append_update(
                {"q": qh[i], "scales": sh[i], "d": d}, u, sc, encoded=True
            )
            ctx.manager.nodes[u].score_history.append(sc)
    ctx.packed_quantized = (q, s, d, unravel)


def _mesh_cohort_stack(ctx: RoundContext, stage: str):
    """``cohort_stack`` with its rows padded to a multiple of the mesh's
    data axis.  The sharded trainer's stack is padded and P-sharded
    already, so it is scored in place with no relayout; the programs
    flatten it themselves where they need to."""
    mesh = _require(ctx, "mesh", stage)
    ndev = dict(mesh.shape).get("data", mesh.devices.size)
    stacked = cohort_stack(ctx)
    return _pad_rows(stacked, jax.tree.leaves(stacked)[0].shape[0], ndev)


class ShardedCommitteeValidator(CommitteeValidator):
    """(3, sharded) the P x Q committee score matrix shard_mapped over the
    mesh's data axis — each device scores its P-shard of candidates; only
    the (P, Q) matrix is gathered at the stage boundary.  Consensus
    bookkeeping (collusion overlay, median acceptance, trigger) is
    inherited unchanged from ``CommitteeValidator``."""

    def _scores_device(self, ctx: RoundContext):
        score_fn = _require(ctx, "sharded_score_fn", "committee_sharded")
        stacked = _mesh_cohort_stack(ctx, "committee_sharded")
        return score_fn(ctx.params, stacked, ctx.val_x, ctx.val_y)


register("validator", "committee_sharded")(ShardedCommitteeValidator())


class Int8ShardedCommitteeValidator(CommitteeValidator):
    """(3, sharded, opt-in) fused score-from-int8: each device quantizes
    its P-shard of update rows with the chain codec and rebuilds the
    candidates in one fused Pallas read (dequantize in-register, delta
    applied during the base-parameter load) — the committee scores exactly
    the blob a quantizing packer would store, and the f32 (P, D) stack is
    materialized once, never twice."""

    def _scores_device(self, ctx: RoundContext):
        score_fn = _require(
            ctx, "sharded_int8_score_fn", "committee_int8_sharded"
        )
        stacked = _mesh_cohort_stack(ctx, "committee_int8_sharded")
        scores, q, s = score_fn(
            ctx.params, stacked, ctx.val_x, ctx.val_y
        )
        d = int(sum(np.prod(l.shape[1:])
                    for l in jax.tree.leaves(stacked)))
        cache_row_quant(ctx, q, s, d)
        return scores


register("validator", "committee_int8_sharded")(Int8ShardedCommitteeValidator())


@register("aggregator", "fused_int8_sharded")
def aggregate_fused_int8_sharded(ctx: RoundContext) -> None:
    """(4, sharded) fused one-pass aggregation of each device's D-shard of
    the chain's int8 representation; the reduced model block is
    all-gathered into the replicated params."""
    agg_fn = _require(ctx, "sharded_agg_fn", "fused_int8_sharded")
    if ctx.packed_quantized is None:
        raise RuntimeError(
            "fused_int8_sharded aggregator needs a quantizing packer (e.g. "
            "'top_k_int8_sharded') to stage the int8 stack in "
            "ctx.packed_quantized"
        )
    q, s, d, unravel = ctx.packed_quantized
    w = normalize_weights(q.shape[0], None if ctx.weights is None
                          else jax.numpy.asarray(ctx.weights))
    # materialize the all-gather once: the reduced vector becomes the next
    # model block, and every next-round stage (local training dispatch,
    # P x Q scoring) is keyed on replicated params — leaving them
    # D-sharded re-shards each of those programs instead (same pathology
    # as the trainer's gather above)
    out = agg_fn(q, s, w)[:d]
    with stage_span(ctx, "aggregate.wait"):
        flat = np.asarray(out)
    _commit_aggregate(ctx, unravel(flat))
