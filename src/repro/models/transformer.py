"""The composable model stack: embedding -> scanned layer units -> head.

Three entry points:
  * ``forward``     — full-sequence, no cache (training).
  * ``prefill``     — full-sequence, returns logits + a filled decode cache.
  * ``decode_step`` — one token against the cache.

Layer units repeat ``num_units`` times; their parameters are stacked with a
leading unit axis and the forward pass ``lax.scan``s over them, keeping HLO
size independent of depth.  ``cfg.remat`` wraps the unit body in
``jax.checkpoint`` for training.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import mamba as mamba_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.attention import (
    attention_decode_attend,
    attention_decode_qkv,
    attention_forward,
    init_attention,
    write_decode_kv,
)
from repro.models.cache import attn_cache_len, init_layer_cache
from repro.models.config import (
    MLP_DENSE,
    MLP_MOE,
    MLP_NONE,
    MLP_RWKV,
    LayerSpec,
    ModelConfig,
)
from repro.models.layers import (
    apply_conv_pos,
    apply_mlp,
    apply_norm,
    embed_init,
    init_conv_pos,
    init_mlp,
    init_norm,
)
from repro.models.moe import MoEShardingCtx, apply_moe, init_moe


class Batch(NamedTuple):
    """Model inputs.  Any of tokens/embeds may be None depending on frontend."""

    tokens: Optional[jnp.ndarray] = None        # (B,S) int32
    embeds: Optional[jnp.ndarray] = None        # (B,S,D)
    embed_mask: Optional[jnp.ndarray] = None    # (B,S) bool: use embeds here
    positions: Optional[jnp.ndarray] = None     # (B,S) or (3,B,S) int32
    targets: Optional[jnp.ndarray] = None       # (B,S) int32
    loss_mask: Optional[jnp.ndarray] = None     # (B,S) float32


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------


def init_layer(key, spec: LayerSpec, cfg: ModelConfig, dtype, virtual_r: int):
    km, kf = jax.random.split(key)
    p = {"norm1": init_norm(cfg, dtype)}
    if spec.mixer.startswith("attn"):
        p["mixer"] = init_attention(km, cfg, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_mod.init_mamba(km, cfg, dtype)
    elif spec.mixer == "rwkv6":
        p["mixer"] = rwkv_mod.init_rwkv_time_mix(km, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp != MLP_NONE:
        p["norm2"] = init_norm(cfg, dtype)
    if spec.mlp == MLP_DENSE:
        p["mlp"] = init_mlp(kf, cfg, dtype)
    elif spec.mlp == MLP_MOE:
        p["mlp"] = init_moe(kf, cfg, dtype, virtual_r=virtual_r)
    elif spec.mlp == MLP_RWKV:
        p["mlp"] = rwkv_mod.init_rwkv_channel_mix(kf, cfg, dtype)
    return p


def init_model(key, cfg: ModelConfig, *, virtual_r: int = 1) -> dict:
    """Returns the full parameter pytree."""
    dtype = _dtype(cfg)
    k_embed, k_units, k_tail, k_head, k_extra, k_extra2 = jax.random.split(key, 6)
    params: dict = {}
    if cfg.frontend != "audio":
        params["embed"] = embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype=dtype)
    if cfg.frontend == "audio":
        params["mask_emb"] = (
            jax.random.normal(k_extra, (cfg.d_model,)) * 0.02
        ).astype(dtype)
        params["conv_pos"] = init_conv_pos(k_extra2, cfg, dtype)

    def init_unit(k):
        ks = jax.random.split(k, max(len(cfg.unit), 1))
        return tuple(
            init_layer(ks[i], spec, cfg, dtype, virtual_r)
            for i, spec in enumerate(cfg.unit)
        )

    unit_keys = jax.random.split(k_units, max(cfg.num_units, 1))
    if cfg.num_units:
        params["units"] = jax.vmap(init_unit)(unit_keys)
    tail_keys = jax.random.split(k_tail, max(len(cfg.tail), 1))
    params["tail"] = tuple(
        init_layer(tail_keys[i], spec, cfg, dtype, virtual_r)
        for i, spec in enumerate(cfg.tail)
    )
    params["final_norm"] = init_norm(cfg, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, cfg.vocab_size, cfg.d_model, dtype=dtype).T
    return params


# ----------------------------------------------------------------------------
# embedding / head
# ----------------------------------------------------------------------------


def embed_inputs(params, cfg: ModelConfig, batch: Batch) -> jnp.ndarray:
    if cfg.frontend == "audio":
        x = batch.embeds
        if batch.embed_mask is not None:
            # masked-prediction: replace masked frames with the mask embedding
            x = jnp.where(
                batch.embed_mask[..., None], params["mask_emb"][None, None], x
            )
        x = x + apply_conv_pos(params["conv_pos"], x)
        return x
    x = params["embed"][batch.tokens]                      # (B,S,D)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if batch.embeds is not None and batch.embed_mask is not None:
        # VLM: overwrite image-pad slots with projected patch embeddings
        x = jnp.where(batch.embed_mask[..., None], batch.embeds, x)
    return x


def lm_logits(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = apply_norm(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["lm_head"]


# ----------------------------------------------------------------------------
# layer application
# ----------------------------------------------------------------------------


def apply_layer_forward(
    lp: dict,
    spec: LayerSpec,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    ctx: Optional[MoEShardingCtx],
    collect_cache: bool,
    max_len: int,
):
    """Returns (x, aux_loss, cache_entry_or_None)."""
    h = apply_norm(lp["norm1"], x, cfg)
    cache_entry = None
    if spec.mixer.startswith("attn"):
        if collect_cache:
            mixed, krot, vrot = attention_forward(
                lp["mixer"], h, positions, cfg, spec.mixer, return_kv=True,
                ctx=ctx,
            )
            cache_entry = _kv_to_cache(cfg, spec, krot, vrot, positions, max_len)
        else:
            mixed = attention_forward(lp["mixer"], h, positions, cfg,
                                      spec.mixer, ctx=ctx)
    elif spec.mixer == "mamba":
        mixed, state = mamba_mod.mamba_forward(lp["mixer"], h, cfg)
        if collect_cache:
            cache_entry = state
    elif spec.mixer == "rwkv6":
        mixed, tm_state = rwkv_mod.rwkv_time_mix_forward(lp["mixer"], h, cfg)
        if collect_cache:
            cache_entry = {"tm": tm_state}
    else:
        raise ValueError(spec.mixer)
    x = x + mixed

    aux = jnp.zeros((), jnp.float32)
    if spec.mlp != MLP_NONE:
        h2 = apply_norm(lp["norm2"], x, cfg)
        if spec.mlp == MLP_DENSE:
            x = x + apply_mlp(lp["mlp"], h2, cfg)
        elif spec.mlp == MLP_MOE:
            y, aux = apply_moe(lp["mlp"], h2, cfg, getattr(ctx, "moe", ctx))
            x = x + y
        elif spec.mlp == MLP_RWKV:
            y, cm_state = rwkv_mod.rwkv_channel_mix_forward(lp["mlp"], h2, cfg)
            x = x + y
            if collect_cache and cache_entry is not None:
                cache_entry["cm"] = cm_state
    return x, aux, cache_entry


def _kv_to_cache(cfg, spec, k, v, positions, max_len):
    """Pack prefill K/V (B,S,Kv,hd) into a decode cache entry."""
    B, S = k.shape[0], k.shape[1]
    L = attn_cache_len(cfg, spec.mixer, max_len)
    pos2d = positions[0] if positions.ndim == 3 else positions
    if S >= L:
        # keep the last L tokens; ring-buffer slot = pos % L
        k_keep, v_keep, p_keep = k[:, S - L :], v[:, S - L :], pos2d[:, S - L :]
        slots = p_keep % L
        b_idx = jnp.arange(B)[:, None]
        ck = jnp.zeros((B, L) + k.shape[2:], k.dtype).at[b_idx, slots].set(k_keep)
        cv = jnp.zeros((B, L) + v.shape[2:], v.dtype).at[b_idx, slots].set(v_keep)
        cp = jnp.full((B, L), -1, jnp.int32).at[b_idx, slots].set(p_keep)
        return {"k": ck, "v": cv, "pos": cp}
    pad = L - S
    ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cp = jnp.pad(pos2d, ((0, 0), (0, pad)), constant_values=-1)
    return {"k": ck, "v": cv, "pos": cp}


def _unit_read(tree, unit):
    """One unit's view of a stacked cache pytree (the tree itself when
    ``unit`` is None: a plain per-layer cache)."""
    if unit is None:
        return tree
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, unit, 0, keepdims=False), tree
    )


def _unit_write(tree, new, unit):
    """Writes one unit's new state into a stacked cache pytree at ``unit``
    (``new`` itself when ``unit`` is None)."""
    if unit is None:
        return new
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(
            a, n.astype(a.dtype), unit, 0),
        tree, new,
    )


def apply_layer_decode(
    lp: dict,
    spec: LayerSpec,
    x: jnp.ndarray,            # (B,1,D)
    position: jnp.ndarray,     # (B,)
    cache: dict,
    cfg: ModelConfig,
    ctx: Optional[MoEShardingCtx],
    mrope_position: Optional[jnp.ndarray],
    unit=None,
):
    """One layer, one token per slot: returns (x, cache).

    ``cache`` is the layer's plain ``(B, ...)`` cache, or with ``unit`` (a
    traced int32) the layer's stacked ``(num_units, B, ...)`` cache carried
    through the decode scan: the layer writes its new K/V rows or state at
    ``[unit]`` and hands the whole stack on, so no copy of it is emitted."""
    h = apply_norm(lp["norm1"], x, cfg)
    if spec.mixer.startswith("attn"):
        q, k, v = attention_decode_qkv(lp["mixer"], h, position, cfg,
                                       mrope_position)
        new_cache = write_decode_kv(cache, k, v, position, unit)
        own = _unit_read(new_cache, unit)
        mixed = attention_decode_attend(
            lp["mixer"], q, position, own["k"], own["v"], own["pos"],
            cfg, spec.mixer,
        )
    elif spec.mixer == "mamba":
        mixed, state = mamba_mod.mamba_step(lp["mixer"], h, cfg,
                                            _unit_read(cache, unit))
        new_cache = _unit_write(cache, state, unit)
    elif spec.mixer == "rwkv6":
        mixed, tm = rwkv_mod.rwkv_time_mix_step(
            lp["mixer"], h, cfg, _unit_read(cache["tm"], unit))
        new_cache = dict(cache, tm=_unit_write(cache["tm"], tm, unit))
    else:
        raise ValueError(spec.mixer)
    x = x + mixed
    if spec.mlp != MLP_NONE:
        h2 = apply_norm(lp["norm2"], x, cfg)
        if spec.mlp == MLP_DENSE:
            x = x + apply_mlp(lp["mlp"], h2, cfg)
        elif spec.mlp == MLP_MOE:
            y, _ = apply_moe(lp["mlp"], h2, cfg, getattr(ctx, "moe", ctx))
            x = x + y
        elif spec.mlp == MLP_RWKV:
            y, cm = rwkv_mod.rwkv_channel_mix_forward(
                lp["mlp"], h2, cfg, state=_unit_read(new_cache["cm"], unit)
            )
            x = x + y
            new_cache["cm"] = _unit_write(new_cache["cm"], cm, unit)
    return x, new_cache


# ----------------------------------------------------------------------------
# full model
# ----------------------------------------------------------------------------


def _unit_forward(unit_params, x, positions, cfg, ctx, collect_cache, max_len):
    aux_total = jnp.zeros((), jnp.float32)
    caches = []

    def layer_fn(lp, spec, xin):
        xo, aux, ce = apply_layer_forward(
            lp, spec, xin, positions, cfg, ctx, collect_cache, max_len
        )
        if hasattr(ctx, "act"):
            xo = ctx.act(xo)
        return xo, aux, ce

    for i, spec in enumerate(cfg.unit):
        fn = layer_fn
        if cfg.remat == "layer" and not collect_cache:
            # per-layer checkpoint: the unit backward re-materializes one
            # layer's internals at a time instead of the whole unit's
            # (EXPERIMENTS.md §Perf H3 — 8-layer Jamba units OOM otherwise)
            fn = jax.checkpoint(layer_fn, static_argnums=(1,))
        x, aux, ce = fn(unit_params[i], spec, x)
        aux_total = aux_total + aux
        caches.append(ce)
    return x, aux_total, tuple(caches)


def _stack_forward(params, cfg, x, positions, ctx, collect_cache, max_len):
    """Scan over units, then run the tail layers."""
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.num_units:
        def body(carry, unit_params):
            xc, auxc = carry
            xo, aux, caches = _unit_forward(
                unit_params, xc, positions, cfg, ctx, collect_cache, max_len
            )
            return (xo, auxc + aux), caches

        if cfg.remat:
            # outer unit checkpoint always; with remat == "layer" the inner
            # per-layer checkpoints bound the re-backward's working set
            body = jax.checkpoint(body)
        (x, aux_total), unit_caches = jax.lax.scan(
            body, (x, aux_total), params["units"]
        )
    else:
        unit_caches = ()

    tail_caches = []
    for i, spec in enumerate(cfg.tail):
        x, aux, ce = apply_layer_forward(
            params["tail"][i], spec, x, positions, cfg, ctx, collect_cache, max_len
        )
        aux_total = aux_total + aux
        tail_caches.append(ce)
    return x, aux_total, unit_caches, tuple(tail_caches)


def forward(params, cfg: ModelConfig, batch: Batch, ctx=None):
    """Training forward: returns (logits, aux_loss)."""
    x = embed_inputs(params, cfg, batch)
    if hasattr(ctx, "act"):
        x = ctx.act(x)
    positions = batch.positions
    if positions is None:
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, aux, _, _ = _stack_forward(params, cfg, x, positions, ctx, False, 0)
    logits = lm_logits(params, cfg, x)
    if hasattr(ctx, "logits"):
        logits = ctx.logits(logits)
    return logits, aux


def prefill(params, cfg: ModelConfig, batch: Batch, max_len: int, ctx=None):
    """Prefill: returns (logits_last, cache) with the cache filled."""
    x = embed_inputs(params, cfg, batch)
    positions = batch.positions
    if positions is None:
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if hasattr(ctx, "act"):
        x = ctx.act(x)
    x, aux, unit_caches, tail_caches = _stack_forward(
        params, cfg, x, positions, ctx, True, max_len
    )
    logits = lm_logits(params, cfg, x[:, -1:])
    if hasattr(ctx, "logits"):
        logits = ctx.logits(logits)
    return logits, {"units": unit_caches, "tail": tail_caches}


def decode_step(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # (B,1) int32
    position: jnp.ndarray,      # (B,) int32
    cache: dict,
    ctx=None,
    mrope_position: Optional[jnp.ndarray] = None,   # (3,B,1)
    embeds: Optional[jnp.ndarray] = None,           # (B,1,D) frontend decode
):
    """One decode step: returns (logits (B,1,V), new_cache)."""
    if cfg.frontend == "audio":
        raise ValueError("encoder-only architectures have no decode step")
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if embeds is not None:
        x = embeds

    if cfg.num_units:
        # The stacked unit cache rides in the carry: each layer scatters its
        # new rows into it at the unit's index and attends over its own
        # slice, so a donated cache is updated in place.  It must not be a
        # scan output: each output is a fresh buffer, a second whole cache
        # written every tick.
        def body(carry, unit_params):
            xc, unit_cache, u = carry
            layers = list(unit_cache)
            for i, spec in enumerate(cfg.unit):
                xc, layers[i] = apply_layer_decode(
                    unit_params[i], spec, xc, position, layers[i], cfg, ctx,
                    mrope_position, unit=u,
                )
                if hasattr(ctx, "act"):
                    xc = ctx.act(xc)
            return (xc, tuple(layers), u + 1), None

        (x, new_unit_caches, _), _ = jax.lax.scan(
            body, (x, cache["units"], jnp.int32(0)), params["units"]
        )
    else:
        new_unit_caches = ()

    new_tail = []
    for i, spec in enumerate(cfg.tail):
        x, nc = apply_layer_decode(
            params["tail"][i], spec, x, position, cache["tail"][i], cfg, ctx,
            mrope_position,
        )
        new_tail.append(nc)
    logits = lm_logits(params, cfg, x)
    if hasattr(ctx, "logits"):
        logits = ctx.logits(logits)
    return logits, {"units": new_unit_caches, "tail": tuple(new_tail)}
