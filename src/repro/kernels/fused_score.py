"""Pallas TPU kernel: fused candidate materialization for committee scoring.

The committee scores P candidate models ``global + update_i`` (paper
§III.B).  When updates live in the chain's int8 representation, the staged
path pays two f32 materializations of the (P, D) stack per validation
call:

  staged:  dequantize kernel   — P*B int8 read,  P*B f32 write
           add base params     — P*B f32 read + B f32 read, P*B f32 write
                                                 ~= 13*P*B bytes total
  fused:   P*B int8 read + B f32 read (base) + P*B f32 write (candidates)
                                                 ~=  5*P*B bytes total

This kernel streams each int8 update tile plus its per-tile scale into
VMEM, dequantizes **in-register**, and applies the delta during the base
parameter load — the candidate stack is written once and the intermediate
f32 update stack never exists.  It is the validation-side mirror of
``fused_agg``'s one-pass aggregation (PR 1): the quantized chain path
never materializes the f32 (P, D) stack twice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize import scale_spec
from repro.kernels.tiling import BLOCK_D


def _fused_candidates(p_ref, s_ref, q_ref, o_ref):
    # p_ref (1, BLOCK_D) f32 base params tile; q_ref (K, BLOCK_D) int8
    # update tiles; s_ref (K, 1) f32 per-tile scales
    o_ref[...] = p_ref[0, :] + q_ref[...].astype(jnp.float32) * s_ref[...]


def make_fused_candidates_fn(*, interpret: bool):
    """Unjitted ``(base, qstack, scales) -> candidates`` closure over the
    static kernel knobs — the form the validation score programs compose
    (``repro.fl.client._int8_score_program``: single-device jitted and
    shard_mapped per P-shard; ``fused_candidates_kernel`` below is the
    same closure jitted for direct use from ``ops``)."""
    return functools.partial(_fused, interpret=interpret)


def _fused(base: jnp.ndarray, qstack: jnp.ndarray, scales: jnp.ndarray,
           *, interpret: bool):
    K, D = qstack.shape
    assert D % BLOCK_D == 0, D
    assert qstack.dtype == jnp.int8, qstack.dtype
    assert base.shape == (D,), (base.shape, D)
    nblk = D // BLOCK_D
    assert scales.shape == (nblk, K, 1), (scales.shape, K, nblk)
    return pl.pallas_call(
        _fused_candidates,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),   # base params tile
            scale_spec(K),                                  # this tile's scales
            pl.BlockSpec((K, BLOCK_D), lambda i: (0, i)),   # int8 tiles
        ],
        out_specs=pl.BlockSpec((K, BLOCK_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, D), jnp.float32),
        interpret=interpret,
    )(base.reshape(1, D), scales, qstack)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_candidates_kernel(base: jnp.ndarray, qstack: jnp.ndarray,
                            scales: jnp.ndarray, *, interpret: bool):
    """base: (D,) f32 global params; qstack: (K, D) int8 update rows;
    scales: (D // BLOCK_D, K, 1) f32.  Returns (K, D) f32 candidate rows
    ``base + dequant(qstack_k)`` in a single grid pass over the stack."""
    return _fused(base, qstack, scales, interpret=interpret)
