"""Pallas TPU kernel: weighted FedAvg aggregation of K client updates.

The per-round aggregation is BFLC's compute hot spot at scale: K flattened
update vectors (K, D) with D = model size (10^7..10^11) reduced to (D,) with
committee-score weights.  The reduction is memory-bound; the kernel streams
(K, BLOCK_D) tiles through VMEM and emits one (BLOCK_D,) tile per grid step,
so HBM traffic is exactly one read of the stack + one write of the result.

Tiling: BLOCK_D = 2048 lanes (16 x 128 — lane-aligned for the VPU); the full
K (committee k is small, <= 64 in practice) fits the sublane dim of one tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import BLOCK_D


def _fedavg_kernel(w_ref, x_ref, o_ref):
    # x_ref: (K, BLOCK_D) VMEM tile; w_ref: (K, 1); o_ref: (1, BLOCK_D)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # (K, 1)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_agg_kernel(stack: jnp.ndarray, weights: jnp.ndarray,
                      *, interpret: bool) -> jnp.ndarray:
    """stack: (K, D) f32, weights: (K,) normalized.  Returns (D,) f32.

    D must be a multiple of BLOCK_D (ops.py pads)."""
    K, D = stack.shape
    assert D % BLOCK_D == 0, D
    grid = (D // BLOCK_D,)
    out = pl.pallas_call(
        _fedavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, BLOCK_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(weights.reshape(K, 1), stack)
    return out[0]
