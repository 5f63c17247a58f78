"""Client-side local training, batched across clients with vmap.

The paper's round has P trainers each running local SGD from the same global
model.  We stack all P clients' sampled batches into (P, steps, b, ...) and
``vmap`` the whole local-training loop — one XLA program trains every client
of the round at once (this is also exactly the structure the sharded
production path distributes over the mesh's data axis).

Committee validation is the same trick: the (P updates x Q members) accuracy
matrix — the P*Q cost term of §V.A — is one batched call, each candidate
model materialized once and evaluated on all Q member batches in a single
batched forward (and the same program shard_maps over the mesh's data axis
for the multi-device engine).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.adapter import ModelAdapter


def make_one_client_fn(adapter: ModelAdapter, lr: float, momentum: float = 0.0):
    """The single-client local-SGD program: (params, xs, ys) -> update.

    xs: (steps, batch, ...), ys: (steps, batch).  Both the vmapped
    single-device trainer and the shard_mapped multi-device trainer wrap
    exactly this function, so their per-client math is identical."""

    def one_client(params, xs, ys):
        def step(carry, xy):
            p, mu = carry
            x, y = xy
            g = jax.grad(adapter.loss)(p, x, y)
            mu = jax.tree.map(lambda m, gg: momentum * m + gg, mu, g)
            p = jax.tree.map(lambda pp, m: pp - lr * m, p, mu)
            return (p, mu), None

        mu0 = jax.tree.map(jnp.zeros_like, params)
        (final, _), _ = jax.lax.scan(step, (params, mu0), (xs, ys))
        return jax.tree.map(lambda a, b: a - b, final, params)

    return one_client


def make_local_train_fn(adapter: ModelAdapter, lr: float, momentum: float = 0.0):
    """Returns train(params, xs, ys) vmapped over a leading client axis.

    xs: (P, steps, batch, ...), ys: (P, steps, batch).  Output: update pytree
    stacked over P (update = locally-trained params - global params)."""
    one_client = make_one_client_fn(adapter, lr, momentum)
    return jax.jit(jax.vmap(one_client, in_axes=(None, 0, 0)))


def make_sharded_local_train_fn(adapter: ModelAdapter, lr: float, mesh,
                                momentum: float = 0.0, axis: str = "data"):
    """The P-client vmapped program shard_mapped over the mesh's data axis.

    Each device scans its (P / ndev)-client shard of the stacked batches
    (params replicated in, update stack sharded out over the leading client
    axis — one all-gather when the host unstacks).  The caller pads P to a
    multiple of the axis size; per-client results are independent, so the
    padded rows are sliced off without affecting real clients."""
    from jax.sharding import PartitionSpec as P

    from repro.shard_compat import shard_map

    vmapped = jax.vmap(make_one_client_fn(adapter, lr, momentum),
                       in_axes=(None, 0, 0))
    return jax.jit(shard_map(
        vmapped, mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=P(axis),
    ))


def _score_matrix_program(adapter: ModelAdapter):
    """The unjitted (params, updates, val_x, val_y) -> (P, Q) program.

    Per candidate i, ``params + update_i`` is materialized exactly once —
    hoisted out of the member axis — and all Q member val batches are
    evaluated in one batched forward on that shared candidate (the member
    vmap carries the data axis only; the weights stay unbatched, so XLA
    folds the Q batches into a single forward).  Both the single-device
    validator and the shard_mapped multi-device validator wrap exactly
    this function, so a P-shard's score rows are bitwise identical to the
    single-device oracle's."""

    def one_candidate(params, update, vx, vy):
        candidate = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, update)
        return jax.vmap(adapter.accuracy, in_axes=(None, 0, 0))(candidate, vx, vy)

    def score(params, updates, vx, vy):
        return jax.vmap(one_candidate, in_axes=(None, 0, None, None))(
            params, updates, vx, vy
        )

    return score


def make_score_matrix_fn(adapter: ModelAdapter):
    """Returns score(params, updates, val_x, val_y) -> (P, Q) accuracies.

    updates: P-stacked pytree; val_x: (Q, vb, ...), val_y: (Q, vb).
    Entry [i, j] = accuracy of (global + update_i) on member j's data —
    the committee's minimized validation approach (§III.B)."""
    return jax.jit(_score_matrix_program(adapter))


def make_sharded_score_matrix_fn(adapter: ModelAdapter, mesh, axis: str = "data"):
    """The P x Q score-matrix program shard_mapped over the mesh's data axis.

    The update stack arrives P-sharded (each device scores its own
    candidate rows against the replicated params + member val batches);
    only the (P, Q) score matrix itself is gathered at the stage boundary
    — the candidate pytrees never leave their shard.  The caller pads P to
    a multiple of the axis size (mirroring the trainer's `_pad_clients`);
    score rows are independent, so padded rows are sliced off without
    affecting real candidates."""
    from jax.sharding import PartitionSpec as P

    from repro.shard_compat import shard_map

    return jax.jit(shard_map(
        _score_matrix_program(adapter), mesh=mesh,
        in_specs=(P(), P(axis), P(), P()),
        out_specs=P(axis),
    ))


def _int8_score_program(adapter: ModelAdapter, unravel, interpret: bool):
    """Unjitted (params, stack, vx, vy) -> ((rows, Q) scores, q, scales).

    ``stack``: (rows, D) f32 flattened updates.  Each row is quantized with
    the chain codec's tiling (so the committee scores exactly the int8 blob
    that would land on chain), then the fused Pallas pass rebuilds every
    candidate in one read — int8 row dequantized in-register and the delta
    applied during the base-parameter load — so the f32 (rows, D) candidate
    stack is materialized once, not twice (PR 1's fused-aggregation trick
    applied to validation).  The per-row ``(q, scales)`` come back with the
    scores: they ARE the chain blobs a quantizing packer would store, so
    the validator caches them on the RoundContext and the packer never
    re-quantizes (carried ROADMAP follow-up)."""
    from jax.flatten_util import ravel_pytree

    from repro.kernels.fused_score import make_fused_candidates_fn
    from repro.kernels.ops import _pad_to_block, scales_from_tiles
    from repro.kernels.quantize import quantize_stack_kernel

    fused_candidates = make_fused_candidates_fn(interpret=interpret)

    def score(params, stack, vx, vy):
        D = stack.shape[1]
        q, tiles = quantize_stack_kernel(_pad_to_block(stack)[0],
                                         interpret=interpret)
        flat, _ = ravel_pytree(params)
        base = _pad_to_block(flat.astype(jnp.float32))[0]
        cands = fused_candidates(base, q, tiles)

        def one_candidate(row, vx, vy):
            candidate = unravel(row[:D])
            return jax.vmap(adapter.accuracy, in_axes=(None, 0, 0))(
                candidate, vx, vy
            )

        scores = jax.vmap(one_candidate, in_axes=(0, None, None))(
            cands, vx, vy
        )
        return scores, q, scales_from_tiles(tiles)

    return score


def flatten_stacked_updates(stacked):
    """In-program flatten of a P-stacked update pytree -> (P, D) f32.

    ``jax.tree.leaves`` order matches ``ravel_pytree`` (both walk the same
    treedef) and per-leaf ``reshape(P, -1)`` matches per-row C-order ravel,
    so row i equals ``ravel_pytree(update_i)`` bit-for-bit — the int8
    scorer can consume the trainer's device-resident ``ctx.cohort_stacked``
    without the host-side flatten round-trip (carried ROADMAP follow-up)."""
    leaves = jax.tree.leaves(stacked)
    return jnp.concatenate(
        [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves],
        axis=1,
    )


def make_score_from_int8_fn(adapter: ModelAdapter, unravel):
    """Single-device fused int8 scorer: (params, (P, D) stack, vx, vy) ->
    ((P, Q) accuracies of the quantized candidates, per-row q, scales)."""
    from repro.kernels.ops import _interpret

    return jax.jit(_int8_score_program(adapter, unravel, _interpret()))


def make_sharded_score_from_int8_fn(adapter: ModelAdapter, mesh, unravel,
                                    axis: str = "data"):
    """The fused int8 scorer shard_mapped over the mesh's data axis: each
    device flattens + quantizes + scores its own P-shard of the stacked
    update pytree (rows are tile-local, so per-row blobs — and therefore
    scores — are bitwise identical to the single-device int8 scorer); the
    (P, Q) score matrix and the P-sharded (q, scales) rows are gathered at
    the stage boundary.  Takes the trainer's stacked update pytree
    directly (``ctx.cohort_stacked`` stays device-resident, P-sharded on
    this mesh, zero relayout); the caller pads P to a multiple of the axis
    size."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ops import _interpret
    from repro.shard_compat import shard_map

    program = _int8_score_program(adapter, unravel, _interpret())

    def score(params, stacked, vx, vy):
        return program(params, flatten_stacked_updates(stacked), vx, vy)

    return jax.jit(shard_map(
        score, mesh=mesh,
        in_specs=(P(), P(axis), P(), P()),
        out_specs=(P(axis), P(axis), P(axis)),
    ))


def make_eval_fn(adapter: ModelAdapter, eval_batch: int = 512):
    @jax.jit
    def _acc(params, x, y):
        return adapter.accuracy(params, x, y)

    def evaluate(params, images, labels) -> float:
        accs, n = [], len(labels)
        for i in range(0, n, eval_batch):
            accs.append(
                float(_acc(params, images[i : i + eval_batch], labels[i : i + eval_batch]))
                * min(eval_batch, n - i)
            )
        return sum(accs) / n

    return evaluate


def sample_client_batches(
    rng: np.random.Generator,
    images: np.ndarray,
    labels: np.ndarray,
    steps: int,
    batch: int,
) -> Tuple[np.ndarray, np.ndarray]:
    idx = rng.integers(0, len(labels), (steps, batch))
    return images[idx], labels[idx]
