"""Pallas TPU kernels: sorting-network robust aggregators over K client
updates — coordinate-wise median (CwMed, Yin et al. 2018, the paper's
robust-aggregation baseline of Fig. 4) and coordinate-wise trimmed mean.

TPU adaptation (DESIGN.md §4): a CUDA CwMed sorts each coordinate in a
thread's registers (data-dependent branches, fine on GPU).  TPU VPU lanes
have no per-lane control flow, so we sort the K *rows* of a (K, BLOCK_D)
VMEM tile with an **odd-even transposition network**: K static phases of
vectorized min/max — branch-free, lane-parallel across all BLOCK_D
coordinates at once.  K is the committee's update count (small), so the
O(K^2) compare-exchanges are negligible against the HBM stream.  The same
network serves both statistics: median takes the middle sorted row(s),
trimmed mean averages rows[trim : K-trim].
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import BLOCK_D


def sort_rows(rows: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """Odd-even transposition network: after K phases rows are sorted
    ascending per lane.  Static unrolled — branch-free on the VPU."""
    K = len(rows)
    rows = list(rows)
    for phase in range(K):
        start = phase % 2
        for i in range(start, K - 1, 2):
            lo = jnp.minimum(rows[i], rows[i + 1])
            hi = jnp.maximum(rows[i], rows[i + 1])
            rows[i], rows[i + 1] = lo, hi
    return rows


def median_of_sorted(rows: List[jnp.ndarray]) -> jnp.ndarray:
    K = len(rows)
    if K % 2 == 1:
        return rows[K // 2]
    return 0.5 * (rows[K // 2 - 1] + rows[K // 2])


def trimmed_mean_of_sorted(rows: List[jnp.ndarray], trim: int) -> jnp.ndarray:
    K = len(rows)
    keep = rows[trim : K - trim]
    acc = keep[0]
    for r in keep[1:]:
        acc = acc + r
    return acc / float(len(keep))


def _cwmed_kernel(x_ref, o_ref, *, K: int):
    rows = sort_rows([x_ref[k, :].astype(jnp.float32) for k in range(K)])
    o_ref[0, :] = median_of_sorted(rows)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cwmed_kernel(stack: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """stack: (K, D) f32 -> (D,) f32 per-coordinate median."""
    K, D = stack.shape
    assert D % BLOCK_D == 0, D
    out = pl.pallas_call(
        functools.partial(_cwmed_kernel, K=K),
        grid=(D // BLOCK_D,),
        in_specs=[pl.BlockSpec((K, BLOCK_D), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(stack)
    return out[0]


def _trimmed_mean_kernel(x_ref, o_ref, *, K: int, trim: int):
    rows = sort_rows([x_ref[k, :].astype(jnp.float32) for k in range(K)])
    o_ref[0, :] = trimmed_mean_of_sorted(rows, trim)


@functools.partial(jax.jit, static_argnames=("trim", "interpret"))
def trimmed_mean_kernel(stack: jnp.ndarray, *, trim: int,
                        interpret: bool) -> jnp.ndarray:
    """stack: (K, D) f32 -> (D,) f32 coordinate-wise trimmed mean."""
    K, D = stack.shape
    assert D % BLOCK_D == 0, D
    if not 0 <= 2 * trim < K:
        raise ValueError(f"trim={trim} too large for K={K}")
    out = pl.pallas_call(
        functools.partial(_trimmed_mean_kernel, K=K, trim=trim),
        grid=(D // BLOCK_D,),
        in_specs=[pl.BlockSpec((K, BLOCK_D), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(stack)
    return out[0]
