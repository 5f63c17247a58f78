"""Pallas TPU kernels: per-tile symmetric int8 quantize / dequantize for
on-chain update storage (paper §IV.D storage optimization).

Updates stored as update blocks dominate chain growth; int8 with a per-tile
f32 scale cuts payload bytes ~4x and — beyond the paper — also cuts the HBM
/ ICI bytes of shipping updates to the committee.  One (K, BLOCK_D) tile per
grid step; scale = max|x| / 127 per row and tile.

Scale layout: the kernels read and write scales as a ``(nblk, K, 1)`` array,
one ``(K, 1)`` block per grid step with the tile axis squeezed.  The TPU
lowering requires the last two block dims to be (8, 128)-aligned or equal
to the array's, which a ``(K, 1)`` column block of the chain's ``(K, nblk)``
layout is not.  ``ops`` converts between the two layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import BLOCK_D


def scale_spec(K: int) -> pl.BlockSpec:
    """This grid step's ``(K, 1)`` block of a ``(nblk, K, 1)`` scale array."""
    return pl.BlockSpec((None, K, 1), lambda i: (i, 0, 0))


def quantize_rows(x: jnp.ndarray):
    """(rows, BLOCK_D) f32 -> (int8 tile, (rows, 1) scales) in-register."""
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _quant_stack_kernel(x_ref, q_ref, s_ref):
    q_ref[...], s_ref[...] = quantize_rows(x_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_stack_kernel(stack: jnp.ndarray, *, interpret: bool):
    """stack: (K, D) f32 -> (q (K, D) int8, scales (D // BLOCK_D, K, 1) f32).

    One grid pass quantizes all K rows tile-by-tile — the codec for packing
    a whole round's update blocks onto the chain in one kernel launch."""
    K, D = stack.shape
    assert D % BLOCK_D == 0, D
    nblk = D // BLOCK_D
    return pl.pallas_call(
        _quant_stack_kernel,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((K, BLOCK_D), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((K, BLOCK_D), lambda i: (0, i)), scale_spec(K)],
        out_shape=[
            jax.ShapeDtypeStruct((K, D), jnp.int8),
            jax.ShapeDtypeStruct((nblk, K, 1), jnp.float32),
        ],
        interpret=interpret,
    )(stack)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_kernel(q: jnp.ndarray, scales: jnp.ndarray,
                      *, interpret: bool) -> jnp.ndarray:
    """q: (D,) int8, scales: (D // BLOCK_D, 1, 1) f32 -> (D,) f32."""
    D = q.shape[0]
    assert D % BLOCK_D == 0, D
    nblk = D // BLOCK_D
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)), scale_spec(1)],
        out_specs=pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(q.reshape(1, D), scales)
    return out[0]
