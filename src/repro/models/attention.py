"""Grouped-query attention with RoPE / M-RoPE, causal, bidirectional and
sliding-window masking, plus a KV cache for decode.

Three execution paths:

* ``_dense_attention``  — materializes (S_q, S_kv) scores; used for short
  sequences (<= DENSE_MAX) and for single-token decode over a cache the
  ragged kernel does not cover (``ragged_block``).
* the ragged decode kernel (``kernels/decode_attention.py``) — single-token
  decode that reads only the cache blocks each live slot holds.
* ``_chunked_attention`` — flash-style online-softmax over KV blocks via
  ``lax.scan`` (outer scan over Q blocks, inner over KV blocks).  Never
  materializes more than (q_block, kv_block) scores, so 32k prefill and the
  500k decode cache fit in the dry-run memory analysis.  The inner scan
  computes the full rectangle and masks — i.e. causal block skipping is NOT
  done in the baseline; see EXPERIMENTS.md §Perf where this is one of the
  hillclimb levers.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import decode_attention
from repro.models.config import (
    ATTN,
    ATTN_GLOBAL,
    ATTN_LOCAL,
    ATTN_SWA,
    MAMBA,
    RWKV,
    ModelConfig,
)
from repro.models.layers import apply_mrope, apply_rope, dense_init

DENSE_MAX = 2048     # max sequence length for the dense path
Q_BLOCK = 512
KV_BLOCK = 512

NEG_INF = -1e30


def is_windowed(mixer: str) -> bool:
    return mixer in (ATTN_SWA, ATTN_LOCAL)


# ----------------------------------------------------------------------------
# params
# ----------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.num_heads * hd, dtype=dtype),
        "wk": dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd, dtype=dtype),
        "wv": dense_init(kv, cfg.d_model, cfg.num_kv_heads * hd, dtype=dtype),
        "wo": dense_init(ko, cfg.num_heads * hd, cfg.d_model, dtype=dtype),
    }
    if cfg.attention_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


# ----------------------------------------------------------------------------
# masking
# ----------------------------------------------------------------------------


def _pair_mask(
    q_pos: jnp.ndarray,   # (..., Sq)
    kv_pos: jnp.ndarray,  # (..., Skv)  (absolute positions; -1 = invalid slot)
    *,
    causal: bool,
    window: int,
) -> jnp.ndarray:
    """Boolean (..., Sq, Skv) mask — True where attention is allowed."""
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    ok = k >= 0
    if causal:
        ok = ok & (k <= q)
    if window > 0:
        ok = ok & (q - k < window)
    return ok


# ----------------------------------------------------------------------------
# core attention computations
# ----------------------------------------------------------------------------


def _dense_attention(q, k, v, mask, softcap: float) -> jnp.ndarray:
    """q: (B,Sq,H,Dh); k,v: (B,Skv,Kv,Dh); mask: (B,Sq,Skv) bool."""
    B, Sq, H, Dh = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qf = q.astype(jnp.float32) * (Dh ** -0.5)
    qg = qf.reshape(B, Sq, Kv, G, Dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def _chunked_attention(
    q, k, v, q_pos, kv_pos, *, causal: bool, window: int, softcap: float
) -> jnp.ndarray:
    """Flash-style attention: outer scan over Q blocks, inner over KV blocks.

    q: (B,Sq,H,Dh), k/v: (B,Skv,Kv,Dh).  Sq % Q_BLOCK == 0, Skv % KV_BLOCK == 0
    (callers pad).  q_pos: (B,Sq), kv_pos: (B,Skv).
    """
    B, Sq, H, Dh = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    nq, nk = Sq // Q_BLOCK, Skv // KV_BLOCK

    qf = (q.astype(jnp.float32) * (Dh ** -0.5)).reshape(B, nq, Q_BLOCK, Kv, G, Dh)
    kf = k.astype(jnp.float32).reshape(B, nk, KV_BLOCK, Kv, Dh)
    vf = v.astype(jnp.float32).reshape(B, nk, KV_BLOCK, Kv, Dh)
    qp = q_pos.reshape(B, nq, Q_BLOCK)
    kp = kv_pos.reshape(B, nk, KV_BLOCK)

    def q_block_body(_, qi):
        qb, qpb = qi            # (B,QB,Kv,G,Dh), (B,QB)

        def kv_body(carry, ki):
            m, l, acc = carry
            kb, vb, kpb = ki    # (B,KB,Kv,Dh), (B,KB,Kv,Dh), (B,KB)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb)  # (B,Kv,G,QB,KB)
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            mask = _pair_mask(qpb, kpb, causal=causal, window=window)
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            scale = jnp.exp(m - m_new)
            l_new = l * scale + p.sum(axis=-1)
            acc_new = acc * scale[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vb
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, Kv, G, Q_BLOCK), NEG_INF, jnp.float32),
            jnp.zeros((B, Kv, G, Q_BLOCK), jnp.float32),
            jnp.zeros((B, Kv, G, Q_BLOCK, Dh), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_body,
            init,
            (
                jnp.moveaxis(kf, 1, 0),
                jnp.moveaxis(vf, 1, 0),
                jnp.moveaxis(kp, 1, 0),
            ),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)   # (B,Kv,G,QB,Dh)
        return None, out

    _, outs = jax.lax.scan(
        q_block_body,
        None,
        (jnp.moveaxis(qf, 1, 0), jnp.moveaxis(qp, 1, 0)),
    )
    # outs: (nq, B, Kv, G, QB, Dh) -> (B, Sq, H, Dh)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ----------------------------------------------------------------------------
# public entry points
# ----------------------------------------------------------------------------


def _project_qkv(params, x, cfg: ModelConfig):
    hd = cfg.resolved_head_dim
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return (
        q.reshape(B, S, cfg.num_heads, hd),
        k.reshape(B, S, cfg.num_kv_heads, hd),
        v.reshape(B, S, cfg.num_kv_heads, hd),
    )


def _rotate(x, positions, cfg: ModelConfig):
    if cfg.rope == "none":
        return x
    if cfg.rope == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    if positions.ndim == 3:  # m-rope style positions on a standard-rope model
        positions = positions[0]
    return apply_rope(x, positions, cfg.rope_theta)


def attention_forward(
    params: dict,
    x: jnp.ndarray,          # (B,S,D)
    positions: jnp.ndarray,  # (B,S) or (3,B,S)
    cfg: ModelConfig,
    mixer: str,
    return_kv: bool = False,
    ctx=None,
):
    """Full-sequence attention (training / prefill, no cache).

    With ``return_kv=True`` also returns the rotated K and V (for prefill
    cache construction)."""
    q, k, v = _project_qkv(params, x, cfg)
    q = _rotate(q, positions, cfg)
    k = _rotate(k, positions, cfg)
    if ctx is not None and hasattr(ctx, "kv"):
        # head-shard Q/K/V when head counts divide the model axis
        q = ctx.q(q)
        k = ctx.kv(k)
        v = ctx.kv(v)
    pos2d = positions[0] if positions.ndim == 3 else positions
    causal = cfg.causal
    window = cfg.sliding_window if is_windowed(mixer) else 0
    S = x.shape[1]
    if S <= DENSE_MAX:
        mask = _pair_mask(pos2d, pos2d, causal=causal, window=window)
        out = _dense_attention(q, k, v, mask, cfg.attn_logit_softcap)
    else:
        assert cfg.attn_logit_softcap == 0, "flash path has no softcap"
        from jax.sharding import PartitionSpec as P

        from repro.models.flash import flash_attention, pick_q_block

        # Expand KV to the full H heads: a single fused head dim carries the
        # model-axis sharding cleanly through every flash einsum.  With the
        # grouped (Kv, G) layout GSPMD cannot express 16-way head sharding
        # across the two split dims and all-gathers the (QB, KB) score blocks
        # in the backward (observed 3.3 TB/device on qwen3 train_4k).
        G = cfg.num_heads // cfg.num_kv_heads
        k_e = jnp.repeat(k, G, axis=2) if G > 1 else k
        v_e = jnp.repeat(v, G, axis=2) if G > 1 else v
        if ctx is not None and hasattr(ctx, "q"):
            k_e = ctx.q(k_e)
            v_e = ctx.q(v_e)
        # block_spec over canonical (B, nq, Kv, G, QB, ...) — see flash.py
        q_block, block_spec, mesh = 512, None, None
        if ctx is not None and getattr(ctx, "model_size", 1) > 1:
            mesh = ctx.mesh
            if ctx.q_spec is not None:     # H % mesh == 0: shard heads
                block_spec = P(ctx.dp, None, ctx.model_axis, None, None, None)
            else:                          # shard the q-block dim instead
                q_block = pick_q_block(S, ctx.model_size)
                block_spec = P(ctx.dp, ctx.model_axis, None, None, None, None)
        # the flash blocks tile the sequence: pad it to whole blocks with
        # keys at position -1 (masked) and drop the pad queries' rows
        pad = (-S) % KV_BLOCK
        if pad:
            seq_pad = lambda t: jnp.pad(
                t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            q, k_e, v_e = seq_pad(q), seq_pad(k_e), seq_pad(v_e)
            pos2d = jnp.pad(pos2d, ((0, 0), (0, pad)), constant_values=-1)
        out = flash_attention(
            q, k_e, v_e, pos2d, pos2d, causal, window, q_block,
            block_spec, mesh,
        )[:, :S]
    B, Sq = out.shape[0], out.shape[1]
    out = out.reshape(B, Sq, -1) @ params["wo"]
    if return_kv:
        return out, k, v
    return out


def attention_decode_qkv(
    params: dict,
    x: jnp.ndarray,            # (B,1,D)
    position: jnp.ndarray,     # (B,) int32 absolute position of the new token
    cfg: ModelConfig,
    mrope_position: Optional[jnp.ndarray] = None,   # (3,B,1) for mrope
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Projects one new token per slot: returns q (B,1,H,Dh) and k, v
    (B,1,Kv,Dh), q and k rotated.  Keys are stored rotated, so the cache
    never needs re-rotation."""
    q, k, v = _project_qkv(params, x, cfg)
    if cfg.rope == "mrope":
        rp = (
            mrope_position
            if mrope_position is not None
            else jnp.broadcast_to(position[None, :, None], (3,) + position.shape + (1,))
        )
        q = apply_mrope(q, rp, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, rp, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope != "none":
        q = apply_rope(q, position[:, None], cfg.rope_theta)
        k = apply_rope(k, position[:, None], cfg.rope_theta)
    return q, k, v


def write_decode_kv(
    cache: dict,               # {"k", "v": (..., B,Sc,Kv,Dh), "pos": (..., B,Sc)}
    k: jnp.ndarray,            # (B,1,Kv,Dh) rotated
    v: jnp.ndarray,            # (B,1,Kv,Dh)
    position: jnp.ndarray,     # (B,)
    unit=None,
) -> dict:
    """Scatters each slot's new K/V row and its position into the cache.

    The leaves are one layer's ``(B, Sc, ...)`` arrays, or with ``unit`` the
    stacked ``(num_units, B, Sc, ...)`` arrays of the decode scan's carry,
    written at ``[unit]``: one row per slot, whatever the stack's size.
    Sliding-window layers use a ring buffer: slot = position % Sc.  For
    full-attention layers Sc == max_len, so the slot is the position.  A
    free slot (position -1) writes nothing: its row index lies past the
    cache and the scatter drops it.
    """
    Sc = cache["pos"].shape[-1]
    slot = jnp.where(position >= 0, position % Sc, Sc)
    idx = (jnp.arange(position.shape[0]), slot)
    if unit is not None:
        idx = (unit,) + idx
    return {
        "k": cache["k"].at[idx].set(k[:, 0], mode="drop"),
        "v": cache["v"].at[idx].set(v[:, 0], mode="drop"),
        "pos": cache["pos"].at[idx].set(position, mode="drop"),
    }


def ragged_block(cfg: ModelConfig, mixer: str, cache_len: int,
                 mesh=None) -> int:
    """Rows per block with which the decode tick reads this layer's cache
    through the ragged kernel (``kernels/decode_attention.py``), or 0 where
    the layer attends densely over every row: a ring-buffer windowed layer,
    soft-capped or bidirectional attention, a cache sharded over a mesh of
    several devices, a model whose decode scan also steps recurrent
    (mamba, rwkv6) layers, or a cache of more than 256 rows that neither
    256 nor 128 divides.

    Beside recurrent layers the kernel slowed the tick: in a Jamba tick on
    a v5e it took 0.03-0.16 ms, but the mamba layers' state and parameter
    copies around it waited 1.96 ms longer, against the 0.57 ms of dense
    attention it replaced."""
    if (is_windowed(mixer) or cfg.attn_logit_softcap > 0 or not cfg.causal
            or (mesh is not None and mesh.size > 1)
            or cfg.has_mixer(MAMBA) or cfg.has_mixer(RWKV)):
        return 0
    for block in (256, 128):
        if cache_len % block == 0:
            return block
    # a cache of another length is one block where it fits in fast memory
    return cache_len if cache_len <= 256 else 0


def attention_decode_attend(
    params: dict,
    q: jnp.ndarray,            # (B,1,H,Dh) rotated
    position: jnp.ndarray,     # (B,) -1 for a free slot
    cache: dict,               # the new row written; see write_decode_kv
    cfg: ModelConfig,
    mixer: str,
    unit=None,
    ctx=None,
) -> jnp.ndarray:
    """Single-token attention over a (possibly ring-buffer) KV cache that
    already holds the new token's row: returns the mixer output (B,1,D).

    ``cache`` is one layer's ``(B, Sc, ...)`` cache or, with ``unit``, the
    decode scan's stacked carry.  Where ``ragged_block`` allows, the kernel
    reads each live slot's rows up to its position straight from the stack;
    else dense attention runs over the unit's whole cache."""
    B, _, _, Dh = q.shape
    block = ragged_block(cfg, mixer, cache["pos"].shape[-1],
                         getattr(ctx, "mesh", None))
    if block:
        stack = cache if unit is not None else jax.tree.map(
            lambda a: a[None], cache)
        qf = q[:, 0].astype(jnp.float32) * (Dh ** -0.5)
        out = decode_attention(
            qf, stack["k"], stack["v"], stack["pos"], position,
            0 if unit is None else unit, block=block,
        ).astype(q.dtype)
    else:
        if unit is not None:
            cache = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, unit, 0, False),
                cache)
        window = cfg.sliding_window if is_windowed(mixer) else 0
        # q_len == 1: dense attention is O(B*H*Skv) — no S^2 blowup — and
        # the softmax reduction over a seq-sharded cache lowers to small
        # psums (a blocked scan would dynamic-slice the sharded seq axis and
        # force XLA to replicate the whole cache per step).
        mask = _pair_mask(position[:, None], cache["pos"], causal=cfg.causal,
                          window=window)
        out = _dense_attention(q, cache["k"], cache["v"], mask,
                               cfg.attn_logit_softcap)
    return out.reshape(B, 1, -1) @ params["wo"]
