"""Sharded train / prefill / decode steps for the production mesh.

Two training flavours:

* ``standard`` — plain token-mean cross-entropy (the Basic-FL / centralized
  baseline at scale).
* ``bflc``     — the paper's technique as a first-class distributed feature:
  the global batch is split into **cohorts** (the production analogue of FL
  trainer nodes — one cohort per data-axis slice by default) and a
  **committee of validation shards** scores each cohort; the median member
  score gates/weights each cohort's loss contribution, so the aggregated
  gradient is exactly the committee-weighted FedAvg of per-cohort gradients
  (gradient linearity), computed by GSPMD with no manual collectives.
  Scoring follows §III.B adapted to in-graph form (DESIGN.md §4): member j
  scores cohort c by -|loss_c - val_loss_j| similarity, median over j,
  softmax over cohorts.  Malicious/poisoned cohorts show anomalous loss and
  are downweighted — the same robustness mechanism the FL runtime implements
  exactly at node granularity.

All steps take explicit in/out shardings from shardings.py; the serving
engine and ``launch/train.py`` jit them.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import dp_axes as mesh_dp_axes
from repro.launch.shardings import ShardingPolicy
from repro.models import decode_step as model_decode_step
from repro.models import forward, prefill as model_prefill
from repro.models.config import ModelConfig
from repro.models.moe import MoEShardingCtx
from repro.models.transformer import Batch
from repro.optim import Optimizer


def make_moe_ctx(cfg: ModelConfig, mesh, pol: ShardingPolicy,
                 *, batch_sharded: bool):
    """Builds the ShardCtx (activation constraints + MoE mesh context)."""
    from repro.models.shardctx import make_shard_ctx

    moe = None
    if cfg.num_experts:
        moe = MoEShardingCtx(
            mesh=mesh,
            dp_axes=pol.dp_axes,
            model_axis=pol.model_axis,
            batch_sharded=batch_sharded,
            tp_over_dp=pol.moe_tp_over_dp,
        )
    return make_shard_ctx(
        mesh, pol.dp_axes, pol.model_axis,
        batch_sharded=batch_sharded, moe=moe,
        num_kv_heads=cfg.num_kv_heads, num_heads=cfg.num_heads,
        seq_parallel=pol.seq_parallel_acts and batch_sharded,
        act_shard_d=getattr(pol, "act_shard_d", False) and batch_sharded,
    )


# ----------------------------------------------------------------------------
# losses
# ----------------------------------------------------------------------------


def token_ce(logits, targets, loss_mask):
    """Per-token NLL.  Written as logsumexp - one_hot·logits (not
    take_along_axis) so a model-sharded vocab axis reduces with psums instead
    of an all-gather of the full logits."""
    z = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(z, axis=-1, keepdims=True))
    z = z - m
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=z.dtype)
    tgt = jnp.einsum("...v,...v->...", z, onehot)
    nll = lse - tgt
    mask = loss_mask.astype(jnp.float32)
    return nll * mask, mask


def standard_loss(params, cfg, batch: Batch, ctx):
    logits, aux = forward(params, cfg, batch, ctx)
    nll, mask = token_ce(logits, batch.targets, batch.loss_mask)
    loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return loss + aux, loss


def bflc_loss(params, cfg, batch: Batch, val_batch: Batch, ctx,
              num_cohorts: int, committee_size: int):
    """Committee-weighted cohort loss (the paper's technique, in-graph)."""
    logits, aux = forward(params, cfg, batch, ctx)
    nll, mask = token_ce(logits, batch.targets, batch.loss_mask)
    B = nll.shape[0]
    nll_c = nll.reshape(num_cohorts, B // num_cohorts, -1)
    mask_c = mask.reshape(num_cohorts, B // num_cohorts, -1)
    cohort_loss = nll_c.sum(axis=(1, 2)) / jnp.maximum(
        mask_c.sum(axis=(1, 2)), 1.0
    )                                                    # (C,)

    # committee validation shards: per-member mean loss under stop_gradient
    vlogits, _ = forward(
        jax.lax.stop_gradient(params), cfg, val_batch, ctx
    )
    vnll, vmask = token_ce(vlogits, val_batch.targets, val_batch.loss_mask)
    member_loss = vnll.sum(axis=-1) / jnp.maximum(vmask.sum(axis=-1), 1.0)
    member_loss = member_loss[:committee_size]           # (Q,)

    # member j's score for cohort c: -|loss_c - val_loss_j|; median over j
    cl = jax.lax.stop_gradient(cohort_loss)
    scores = -jnp.abs(cl[:, None] - member_loss[None, :])   # (C, Q)
    med = jnp.median(scores, axis=1)                        # (C,)
    weights = jax.nn.softmax(med / jnp.maximum(med.std(), 1e-6))
    weights = jax.lax.stop_gradient(weights)

    loss = jnp.sum(weights * cohort_loss)
    return loss + aux, loss


# ----------------------------------------------------------------------------
# train step
# ----------------------------------------------------------------------------


class TrainState(NamedTuple):
    params: dict
    opt_state: dict
    step: jnp.ndarray


def _split_microbatches(batch: Batch, mb: int) -> Batch:
    """Reshape every field's batch dim B -> (mb, B/mb); M-RoPE positions
    (3,B,S) split on axis 1."""

    def split(name, x):
        if x is None:
            return None
        if name == "positions" and x.ndim == 3:
            return jnp.moveaxis(
                x.reshape(x.shape[0], mb, -1, x.shape[2]), 1, 0
            )
        return x.reshape(mb, -1, *x.shape[1:])

    return Batch(**{k: split(k, v) for k, v in batch._asdict().items()})


def make_train_step(
    cfg: ModelConfig,
    optimizer: Optimizer,
    mesh,
    pol: ShardingPolicy,
    *,
    mode: str = "bflc",
    num_cohorts: int = 16,
    committee_size: int = 8,
    num_microbatches: int = 1,
):
    ctx = make_moe_ctx(cfg, mesh, pol, batch_sharded=True)

    def loss_for(p, b: Batch, val_batch):
        if mode == "bflc":
            return bflc_loss(p, cfg, b, val_batch, ctx,
                             num_cohorts, committee_size)
        return standard_loss(p, cfg, b, ctx)

    def train_step(state: TrainState, batch: Batch,
                   val_batch: Optional[Batch] = None):
        if num_microbatches == 1:
            (total, ce), grads = jax.value_and_grad(
                lambda p: loss_for(p, batch, val_batch), has_aux=True
            )(state.params)
        else:
            # gradient accumulation: activation memory scales 1/mb (§Perf H3)
            mbs = _split_microbatches(batch, num_microbatches)
            gacc0 = jax.tree.map(jnp.zeros_like, state.params)

            def body(gacc, mb_batch):
                (tot, ce_mb), g = jax.value_and_grad(
                    lambda p: loss_for(p, mb_batch, val_batch), has_aux=True
                )(state.params)
                gacc = jax.tree.map(
                    lambda a, gg: a + (gg / num_microbatches).astype(a.dtype),
                    gacc, g,
                )
                return gacc, (tot, ce_mb)

            grads, (totals, ces) = jax.lax.scan(body, gacc0, mbs)
            total, ce = totals.mean(), ces.mean()
        new_params, new_opt = optimizer.update(
            grads, state.opt_state, state.params, state.step
        )
        return TrainState(new_params, new_opt, state.step + 1), {
            "loss": ce,
            "total_loss": total,
        }

    return train_step


# ----------------------------------------------------------------------------
# serving steps
# ----------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, mesh, pol: ShardingPolicy,
                      max_len: int, *, batch_sharded: bool = True):
    ctx = make_moe_ctx(cfg, mesh, pol, batch_sharded=batch_sharded)

    def prefill_step(params, batch: Batch):
        return model_prefill(params, cfg, batch, max_len, ctx)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh, pol: ShardingPolicy,
                     *, batch_sharded: bool = True,
                     return_logits: bool = True):
    """One greedy decode step.

    ``return_logits=False`` drops the (B, 1, V) logits from the outputs —
    the serving hot loop only needs the argmax token, and materializing /
    transferring full logits every tick is pure overhead (the continuous
    engine jits this with the token/position/cache buffers donated, so the
    step updates the KV cache in place).
    """
    ctx = make_moe_ctx(cfg, mesh, pol, batch_sharded=batch_sharded)

    def serve_step(params, tokens, position, cache,
                   mrope_position=None):
        logits, new_cache = model_decode_step(
            params, cfg, tokens, position, cache, ctx,
            mrope_position=mrope_position,
        )
        next_token = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        if return_logits:
            return next_token[:, None], logits, new_cache
        return next_token[:, None], new_cache

    return serve_step
