"""Plain reference of one BFLC committee round (arXiv:2004.00773 §III-IV).

Given a round's inputs as the community drew them (the cohorts' clients and
local batches, the committee and its validation batches), it computes what
the round must produce:

* local training: each client's update after ``steps`` steps of SGD with
  heavy-ball momentum (mu = m * mu + g; p = p - lr * mu) from the global
  model;
* committee scoring: the P x Q matrix of accuracies of (model + update_i) on
  member j's validation batch;
* consensus and packing: per update the median over members, accepted when
  at least ``threshold`` times the running mean of accepted medians (the
  first always), the k best accepted by median (ties in arrival order),
  topped up with the best when fewer qualify;
* the int8 chain codec (optional): the update flattened leaf by leaf in
  sorted-key order, cut into tiles of 2048, each stored as round(x / s)
  clipped to +-127 with s = max|x| / 127 (1 for an all-zero tile);
* aggregation: the packed updates averaged with their medians as weights,
  added to the model to give the next model block.

Everything runs in the dtype and at the matmul precision it is given:
float32 at the highest precision is the reference; float32 at ``high``
(three bfloat16 passes) is the lower-precision control.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

TILE = 2048


def _cast(tree, dtype):
    return jax.tree.map(lambda x: jnp.asarray(x).astype(dtype), tree)


class Programs:
    """The reference's local training and scoring, each jitted once for a
    model, its optimiser settings and a dtype."""

    def __init__(self, model, *, lr: float, momentum: float,
                 dtype=jnp.float32):
        def one_client(params, xc, yc):
            p0 = _cast(params, dtype)

            def step(carry, xy):
                p, mu = carry
                g = jax.grad(model.loss)(p, xy[0], xy[1])
                mu = jax.tree.map(
                    lambda m, gg: (momentum * m + gg).astype(dtype), mu, g)
                p = jax.tree.map(lambda pp, m: (pp - lr * m).astype(dtype),
                                 p, mu)
                return (p, mu), None

            mu0 = jax.tree.map(jnp.zeros_like, p0)
            (p, _), _ = jax.lax.scan(step, (p0, mu0), (xc.astype(dtype), yc))
            return jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                - b.astype(jnp.float32), p, p0)

        def one_candidate(params, u, vx, vy):
            cand = jax.tree.map(lambda p, d: (p + d).astype(dtype), params, u)
            return jax.vmap(lambda x, y: model.accuracy(
                cand, x.astype(dtype), y))(vx, vy)

        self.train = jax.jit(jax.vmap(one_client, in_axes=(None, 0, 0)))
        self.score = jax.jit(jax.vmap(one_candidate,
                                      in_axes=(None, 0, None, None)))

    def local_updates(self, params, xs, ys):
        """(P, steps, b, ...) batches -> the P-stacked update pytree."""
        return self.train(params, jnp.asarray(xs), jnp.asarray(ys))

    def score_matrix(self, params, updates, vx, vy) -> np.ndarray:
        """(P, Q) accuracies of params + update_i on member j's batch."""
        return np.asarray(self.score(params, updates, jnp.asarray(vx),
                                     jnp.asarray(vy)))


def consensus(scores: Sequence[np.ndarray], trainers: Sequence[List[int]],
              *, k: int, threshold: float):
    """Per-cohort score matrices -> (packed ids, packed medians, medians)."""
    records, accepted = [], []
    for S, ids in zip(scores, trainers):
        for i, u in enumerate(ids):
            med = float(np.median(S[i]))
            ok = not accepted or med >= threshold * float(np.mean(accepted))
            records.append((u, med, ok))
            if ok:
                accepted.append(med)
    good = sorted([r for r in records if r[2]], key=lambda r: -r[1])[:k]
    if not good:
        good = sorted(records, key=lambda r: -r[1])[:1]
    while len(good) < k:
        good.append(good[0])
    medians = {u: med for u, med, _ in records}
    return [r[0] for r in good], [r[1] for r in good], medians


def flatten(tree) -> np.ndarray:
    """Leaves in sorted-key order, each C-order, concatenated."""
    return np.concatenate([np.asarray(l, np.float32).reshape(-1)
                           for l in jax.tree.leaves(tree)])


def unflatten(flat: np.ndarray, like):
    leaves, treedef = jax.tree.flatten(like)
    out, at = [], 0
    for l in leaves:
        n = int(np.prod(l.shape))
        out.append(flat[at:at + n].reshape(l.shape))
        at += n
    return jax.tree.unflatten(treedef, out)


def int8_roundtrip(flat: np.ndarray) -> np.ndarray:
    """The chain codec's stored value of ``flat``, decoded."""
    d = flat.shape[0]
    x = np.pad(flat.astype(np.float32), (0, (-d) % TILE)).reshape(-1, TILE)
    amax = np.abs(x).max(axis=1)
    s = np.where(amax > 0, amax / np.float32(127.0), np.float32(1.0))
    q = np.clip(np.round(x / s[:, None]), -127, 127)
    return (q * s[:, None]).reshape(-1)[:d].astype(np.float32)


def aggregate(params, updates: Dict[int, Any], packed: List[int],
              weights: List[float], *, int8: bool):
    """The next model block: params + sum_i w_i * stored(update_i)."""
    w = np.asarray(weights, np.float64)
    w = w / max(w.sum(), 1e-12)
    total = np.zeros_like(flatten(params), dtype=np.float64)
    for wi, u in zip(w, packed):
        row = flatten(updates[u])
        total += wi * (int8_roundtrip(row) if int8 else row)
    return unflatten((flatten(params) + total).astype(np.float32), params)


def run_round(programs: Programs, params, rnd: Dict[str, Any],
              rc: Dict[str, Any], hook=None):
    """One round from ``params`` on the recorded inputs ``rnd``; returns
    dict(updates (cohort 0, stacked), scores (cohort 0), packed, medians,
    new_params, per-uploader updates).  ``hook(name, value)`` may replace
    an intermediate (the planted faults of the calibration): it is called
    as ``hook(name, value, params)`` and returns the value to go on with."""
    hook = hook or (lambda name, value, params: value)
    cohort_updates, cohort_scores, by_id = [], [], {}
    for c in rnd["cohorts"]:
        U = programs.local_updates(params, c["xs"], c["ys"])
        U = hook("updates", U, params)
        S = programs.score_matrix(params, U, rnd["val_x"], rnd["val_y"])
        cohort_updates.append(U)
        cohort_scores.append(S)
        for i, u in enumerate(c["trainers"]):
            by_id[u] = jax.tree.map(lambda x: np.asarray(x[i]), U)
    packed, meds, medians = consensus(
        cohort_scores, [c["trainers"] for c in rnd["cohorts"]],
        k=rc["k_updates"], threshold=rc["accept_threshold"])
    packed, meds = hook("packed", (packed, meds), params)
    weights = meds if rc.get("weight_by_score", True) else [1.0] * len(meds)
    new = aggregate(params, by_id, packed, weights,
                    int8=bool(rc.get("quantize_chain")))
    new = hook("new_params", new, params)
    return {"updates": cohort_updates[0], "scores": cohort_scores[0],
            "packed": packed, "medians": medians, "new_params": new}
