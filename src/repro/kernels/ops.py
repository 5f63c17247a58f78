"""jit'd public wrappers around the Pallas kernels.

This is the method-dispatch layer of the aggregation engine: callers hand
over a f32 ``(K, D)`` stack *or* the chain's quantized representation
(int8 stack + per-tile scales) and name a reduction; padding to the tile
boundary happens exactly once here, the interpret flag is picked off-TPU,
and pytree-level helpers adapt model updates.

  aggregate(stack, method=..., weights=..., trim=...)        f32 path
  aggregate_quantized(q, scales, method=..., ...)            fused int8 path
  quantize_stack(stack)                                      round codec
  decode_attention(q, k, v, pos, q_pos, unit, block=...)     serving tick

The sharded multi-device engine builds its programs once through the
factories at the bottom (``make_quantize_stack_sharded`` /
``make_aggregate_quantized_sharded``): each device runs the same Pallas
kernels on its D-shard of the int8 stack — tile-aligned by construction,
so per-shard results are bitwise identical to the single-device tiles.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cwmed import cwmed_kernel, trimmed_mean_kernel
from repro.kernels.decode_attention import ragged_decode_attention_kernel
from repro.kernels.fedavg_agg import fedavg_agg_kernel
from repro.kernels.tiling import BLOCK_D
from repro.kernels.fused_agg import METHODS, fused_agg_kernel
from repro.kernels.quantize import dequantize_kernel, quantize_stack_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def scales_to_tiles(scales: jnp.ndarray) -> jnp.ndarray:
    """Chain scale layout (K, nblk) -> the kernels' (nblk, K, 1) layout."""
    return scales.T[:, :, None]


def scales_from_tiles(tiles: jnp.ndarray) -> jnp.ndarray:
    """The kernels' (nblk, K, 1) scale layout -> chain layout (K, nblk)."""
    return tiles[:, :, 0].T


def padded_dim(d: int) -> int:
    """Smallest multiple of BLOCK_D >= d."""
    return d + (-d) % BLOCK_D


def _pad_to_block(x: jnp.ndarray, axis: int = -1) -> Tuple[jnp.ndarray, int]:
    d = x.shape[axis]
    pad = (-d) % BLOCK_D
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _normalize_weights(K: int, weights: Optional[jnp.ndarray]) -> jnp.ndarray:
    from repro.core.aggregation import normalize_weights

    return normalize_weights(K, weights)


# ----------------------------------------------------------------------
# method dispatch: f32 stacks
# ----------------------------------------------------------------------
def aggregate(
    stack: jnp.ndarray,
    method: str = "fedavg",
    weights: Optional[jnp.ndarray] = None,
    trim: int = 1,
) -> jnp.ndarray:
    """(K, D) f32 -> (D,) via the Pallas kernels; pads once, dispatches.

    fedavg weights may be unnormalized (e.g. raw committee scores) — they
    are normalized to sum 1 here; use ``fedavg_agg`` for a raw weighted
    sum."""
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r} (want one of {METHODS})")
    K, D = stack.shape
    if method == "fedavg":
        return fedavg_agg(stack, _normalize_weights(K, weights))
    # zero-pad to the tile boundary: reductions are per-lane, so padded
    # lanes only affect padded outputs, which are sliced off below
    padded, _ = _pad_to_block(stack)
    if method == "cwmed":
        out = cwmed_kernel(padded, interpret=_interpret())
    else:
        out = trimmed_mean_kernel(padded, trim=trim, interpret=_interpret())
    return out[:D]


def fedavg_agg(stack: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """(K, D) x (K,) -> (D,) weighted SUM via the Pallas kernel — weights
    are used as-is (callers own normalization)."""
    D = stack.shape[1]
    padded, _ = _pad_to_block(stack)
    out = fedavg_agg_kernel(
        padded, jnp.asarray(weights).astype(jnp.float32),
        interpret=_interpret(),
    )
    return out[:D]


def cwmed(stack: jnp.ndarray) -> jnp.ndarray:
    """(K, D) -> (D,) coordinate-wise median via the Pallas kernel."""
    return aggregate(stack, "cwmed")


def trimmed_mean(stack: jnp.ndarray, trim: int = 1) -> jnp.ndarray:
    """(K, D) -> (D,) coordinate-wise trimmed mean via the Pallas kernel."""
    return aggregate(stack, "trimmed_mean", trim=trim)


def decode_attention(q, k, v, pos, q_pos, unit, *, block: int) -> jnp.ndarray:
    """One query token per slot over the decode scan's stacked slot cache,
    reading only the blocks of ``block`` rows that each live slot holds
    (``kernels/decode_attention.py``): (B, H, Dh) scaled float32 queries,
    (U, B, Sc, Kv, Dh) K and V, (U, B, Sc) positions, (B,) query positions
    (-1 for a free slot) and the layer's unit index -> (B, H, Dh)."""
    return ragged_decode_attention_kernel(q, k, v, pos, q_pos, unit,
                                          block=block, interpret=_interpret())


# ----------------------------------------------------------------------
# quantized representation: codec + fused one-pass aggregation
# ----------------------------------------------------------------------
def quantize(x: jnp.ndarray):
    """(D,) -> (q int8 (D,), scales, D) — chain-storage codec: the K = 1
    case of ``quantize_stack``."""
    q, s, D = quantize_stack(x[None])
    return q[0], s[0], D


def dequantize(q: jnp.ndarray, scales: jnp.ndarray, D: int) -> jnp.ndarray:
    if D == 0:
        return jnp.zeros((0,), jnp.float32)
    out = dequantize_kernel(q, scales.reshape(-1, 1, 1),
                            interpret=_interpret())
    return out[:D]


def quantize_stack(stack: jnp.ndarray):
    """(K, D) f32 -> (q (K, Dpad) int8, scales (K, nblk) f32, D).

    One kernel launch quantizes a whole round's K update vectors; zero-pads
    to the tile boundary (padded lanes quantize to 0 and are never read back
    past D)."""
    K, D = stack.shape
    if D == 0:
        return jnp.zeros((K, 0), jnp.int8), jnp.zeros((K, 0), jnp.float32), 0
    padded, _ = _pad_to_block(stack)
    q, tiles = quantize_stack_kernel(padded, interpret=_interpret())
    return q, scales_from_tiles(tiles), D


def aggregate_quantized(
    q: jnp.ndarray,
    scales: jnp.ndarray,
    D: Optional[int] = None,
    method: str = "fedavg",
    weights: Optional[jnp.ndarray] = None,
    trim: int = 1,
    quantize_out: bool = False,
):
    """Fused one-pass aggregation straight from the chain's int8 blocks.

    q: (K, Dpad) int8, scales: (K, Dpad // BLOCK_D) f32, D: true (unpadded)
    dimension.  Returns (D,) f32 — or, with ``quantize_out``, the quantized
    result ``(q_out (Dpad,) int8, out_scales, D)`` ready for chain storage —
    without ever materializing the f32 (K, D) stack in HBM."""
    K, Dpad = q.shape
    true_d = Dpad if D is None else D
    w = _normalize_weights(K, weights)
    out = fused_agg_kernel(
        q, scales_to_tiles(scales), w, method=method, trim=trim,
        quantize_out=quantize_out, interpret=_interpret(),
    )
    if quantize_out:
        q_out, s_out = out
        return q_out, s_out.reshape(-1), true_d
    return out[:true_d]


def candidates_from_quantized(
    base: jnp.ndarray,
    q: jnp.ndarray,
    scales: jnp.ndarray,
    D: Optional[int] = None,
) -> jnp.ndarray:
    """Fused candidate rebuild straight from the chain's int8 blocks.

    base: (D,) f32 global params; q: (K, Dpad) int8 update rows; scales:
    (K, Dpad // BLOCK_D) f32.  Returns the (K, D) f32 candidate stack
    ``base + dequant(q_k)`` — one int8 read of the stack, dequantized
    in-register with the delta applied during the base-parameter load, so
    the f32 update stack is never materialized (the validation-side mirror
    of ``aggregate_quantized``)."""
    from repro.kernels.fused_score import fused_candidates_kernel

    K, Dpad = q.shape
    true_d = Dpad if D is None else D
    padded, _ = _pad_to_block(base.astype(jnp.float32))
    out = fused_candidates_kernel(padded, q, scales_to_tiles(scales),
                                  interpret=_interpret())
    return out[:, :true_d]


# ----------------------------------------------------------------------
# sharded multi-device engine (one program per mesh, built once)
# ----------------------------------------------------------------------
def padded_dim_sharded(d: int, shards: int) -> int:
    """Smallest multiple of ``shards * BLOCK_D`` >= d.

    Padding to this boundary keeps every D-shard tile-aligned, so the
    per-shard quantization tiles (and their scales) coincide exactly with
    the single-device tiles — the sharded codec differs from the
    single-device codec only in how many all-zero padding tiles trail the
    data."""
    chunk = BLOCK_D * shards
    return d + (-d) % chunk


def make_quantize_stack_sharded(mesh, axis: str = "data"):
    """Sharding-aware round codec: jitted ``(K, D) f32 -> (q, scales)``.

    Pads D to ``padded_dim_sharded(D, ndev)`` and shard_maps
    ``quantize_stack_kernel`` over the mesh's data axis — each device
    quantizes its own (K, Dpad/ndev) slice of the stack, one kernel launch
    per device, no cross-device traffic (tiles are independent)."""
    from jax.sharding import PartitionSpec as P

    from repro.shard_compat import shard_map

    ndev = mesh.shape[axis]
    interpret = _interpret()

    def _shard(chunk):                         # (K, Dpad / ndev) per device
        q, tiles = quantize_stack_kernel(chunk, interpret=interpret)
        return q, scales_from_tiles(tiles)

    sharded = shard_map(_shard, mesh=mesh, in_specs=P(None, axis),
                        out_specs=(P(None, axis), P(None, axis)))

    @jax.jit
    def quantize_sharded(stack: jnp.ndarray):
        D = stack.shape[1]
        pad = padded_dim_sharded(D, ndev) - D
        if pad:
            stack = jnp.pad(stack, ((0, 0), (0, pad)))
        return sharded(stack)

    return quantize_sharded


def make_aggregate_quantized_sharded(mesh, axis: str = "data",
                                     method: str = "fedavg", trim: int = 1):
    """Sharded fused aggregation: jitted ``(q, scales, weights) -> (Dpad,)``.

    Each device runs the fused int8->dequant->reduce kernel on its D-shard
    of the stack (the ROADMAP follow-up); the (Dpad,)-sharded result is
    all-gathered into the replicated model block by XLA at the first
    replicated use (``apply_update``).  ``weights`` must already be
    normalized (``normalize_weights``) and is replicated to every shard so
    the fedavg reduction weighs rows identically everywhere."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels.fused_agg import make_fused_agg_fn
    from repro.shard_compat import shard_map

    fused = make_fused_agg_fn(method=method, trim=trim,
                              interpret=_interpret())

    def _shard(q, scales, weights):            # (K, Dpad / ndev) per device
        return fused(q, scales_to_tiles(scales), weights)

    sharded = shard_map(_shard, mesh=mesh,
                        in_specs=(P(None, axis), P(None, axis), P()),
                        out_specs=P(axis))

    @jax.jit
    def aggregate_sharded(q: jnp.ndarray, scales: jnp.ndarray,
                          weights: jnp.ndarray):
        return sharded(q, scales, weights.astype(jnp.float32))

    return aggregate_sharded


# ----------------------------------------------------------------------
# pytree adapters
# ----------------------------------------------------------------------
def quantize_pytree(tree):
    """Flatten + quantize a model/update pytree for on-chain storage."""
    from jax.flatten_util import ravel_pytree

    flat, unravel = ravel_pytree(tree)
    q, s, D = quantize(flat.astype(jnp.float32))
    return {"q": q, "scales": s, "d": D}, unravel


def dequantize_pytree(blob, unravel):
    return unravel(dequantize(blob["q"], blob["scales"], blob["d"]))


class Int8UpdateCodec:
    """Chain payload codec: update pytree <-> int8 blob dict.

    The unravel structure is fixed at construction from an example pytree
    (all BFLC updates share the model's structure), so decode needs no
    side-channel."""

    def __init__(self, example_pytree):
        from jax.flatten_util import ravel_pytree

        flat, self._unravel = ravel_pytree(example_pytree)
        self.dim = flat.shape[0]

    def encode(self, tree):
        blob, _ = quantize_pytree(tree)
        return blob

    def decode(self, blob):
        return dequantize_pytree(blob, self._unravel)

    def unravel(self, flat: jnp.ndarray):
        return self._unravel(flat)
