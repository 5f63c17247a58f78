"""Pallas TPU kernel: fused quantized aggregation — the BFLC round hot path
in ONE grid pass.

The staged pipeline (dequantize K rows -> materialize the full (K, D) f32
stack in HBM -> fedavg/cwmed kernel -> quantize the result) costs ~3 f32
passes over K*D elements.  At chain-stored int8 precision that is pure
waste: this kernel streams K int8 update tiles plus their per-tile scales
into VMEM, dequantizes **in-register**, reduces (weighted fedavg, coordinate
-wise median, or trimmed mean via the shared odd-even network), and — when
the result goes straight back onto the chain — re-quantizes the output tile
in the same grid step.

HBM traffic per grid step (tile of BLOCK_D lanes, K committee updates):

  staged:  K*B int8 read + K*B f32 write (dequant)
           + K*B f32 read + B f32 write (aggregate)
           + B f32 read + B int8 write  (quant)        ~= 9*K*B bytes total
  fused:   K*B int8 read + B write (f32 or int8)       ~=   K*B bytes total

i.e. one int8 read of the stack + one write of the result — ~12x fewer
bytes on the dominant read than the f32 staged path the runtime used to
run.  Scales ride along in the same pass: one (K, 1) f32 block per tile
(``quantize.scale_spec``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cwmed import (
    median_of_sorted,
    sort_rows,
    trimmed_mean_of_sorted,
)
from repro.kernels.quantize import quantize_rows, scale_spec
from repro.kernels.tiling import BLOCK_D

METHODS = ("fedavg", "cwmed", "trimmed_mean")


def _reduce_tile(w, s, x, *, method: str, trim: int) -> jnp.ndarray:
    """Dequantize a (K, BLOCK_D) int8 tile in-register and reduce to (BLOCK_D,)."""
    K = x.shape[0]
    rows_f = x.astype(jnp.float32) * s          # (K, BLOCK_D): deq in-register
    if method == "fedavg":
        return jnp.sum(rows_f * w, axis=0)
    rows = sort_rows([rows_f[k, :] for k in range(K)])
    if method == "cwmed":
        return median_of_sorted(rows)
    return trimmed_mean_of_sorted(rows, trim)


def _fused_kernel(w_ref, s_ref, x_ref, o_ref, *, method: str, trim: int):
    # x_ref (K, BLOCK_D) int8; s_ref (K, 1) f32 scales; w_ref (K, 1) weights
    o_ref[0, :] = _reduce_tile(
        w_ref[...], s_ref[...], x_ref[...], method=method, trim=trim
    )


def _fused_kernel_qout(w_ref, s_ref, x_ref, q_ref, so_ref, *,
                       method: str, trim: int):
    agg = _reduce_tile(
        w_ref[...], s_ref[...], x_ref[...], method=method, trim=trim
    )
    q_ref[...], so_ref[...] = quantize_rows(agg[None, :])


def make_fused_agg_fn(*, method: str = "fedavg", trim: int = 1,
                      quantize_out: bool = False, interpret: bool):
    """Unjitted ``(qstack, scales, weights) -> out`` closure over the static
    kernel knobs — the form the sharded aggregation path composes under
    ``shard_map`` (each device invokes it on its D-shard of the stack;
    ``fused_agg_kernel`` below is the same closure jitted for direct use)."""
    return functools.partial(
        _fused_agg, method=method, trim=trim,
        quantize_out=quantize_out, interpret=interpret,
    )


def _fused_agg(
    qstack: jnp.ndarray,
    scales: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    method: str = "fedavg",
    trim: int = 1,
    quantize_out: bool = False,
    interpret: bool,
):
    K, D = qstack.shape
    assert D % BLOCK_D == 0, D
    assert qstack.dtype == jnp.int8, qstack.dtype
    nblk = D // BLOCK_D
    assert scales.shape == (nblk, K, 1), (scales.shape, K, nblk)
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}")
    if method == "trimmed_mean" and not 0 <= 2 * trim < K:
        raise ValueError(f"trim={trim} too large for K={K}")

    in_specs = [
        pl.BlockSpec((K, 1), lambda i: (0, 0)),          # weights
        scale_spec(K),                                   # this tile's scales
        pl.BlockSpec((K, BLOCK_D), lambda i: (0, i)),    # int8 tile
    ]
    operands = (weights.reshape(K, 1).astype(jnp.float32), scales, qstack)
    if not quantize_out:
        out = pl.pallas_call(
            functools.partial(_fused_kernel, method=method, trim=trim),
            grid=(nblk,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
            interpret=interpret,
        )(*operands)
        return out[0]
    q, s = pl.pallas_call(
        functools.partial(_fused_kernel_qout, method=method, trim=trim),
        grid=(nblk,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),
                   scale_spec(1)],
        out_shape=[
            jax.ShapeDtypeStruct((1, D), jnp.int8),
            jax.ShapeDtypeStruct((nblk, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return q[0], s


@functools.partial(
    jax.jit, static_argnames=("method", "trim", "quantize_out", "interpret")
)
def fused_agg_kernel(
    qstack: jnp.ndarray,
    scales: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    method: str = "fedavg",
    trim: int = 1,
    quantize_out: bool = False,
    interpret: bool,
):
    """qstack: (K, D) int8; scales: (D // BLOCK_D, K, 1) f32; weights:
    (K,) normalized (ignored unless method == "fedavg").

    Returns (D,) f32, or (q (D,) int8, out_scales (D // BLOCK_D, 1, 1) f32)
    when ``quantize_out`` — everything in a single grid pass over the
    stack."""
    return _fused_agg(qstack, scales, weights, method=method, trim=trim,
                      quantize_out=quantize_out, interpret=interpret)
