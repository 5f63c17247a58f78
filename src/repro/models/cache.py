"""Decode-cache containers for the heterogeneous layer stack.

A model cache is ``{"units": stacked_pytree, "tail": (per-layer, ...)}`` where
the stacked pytree has a leading ``num_units`` axis.  The decode step
``lax.scan``s over the unit params and carries the whole stack: each layer
writes its new rows or state into it at the unit's index, so a donated cache
is updated in place.

Per-layer cache by mixer kind:
  attn / attn_global : {"k": (B, max_len, Kv, hd), "v": ..., "pos": (B, max_len)}
  attn_swa / local   : same, but length min(window, max_len) (ring buffer)
  mamba              : {"conv": (B, dc-1, din), "ssm": (B, din, ds)}
  rwkv6              : {"tm": {shift, wkv}, "cm": {shift}}
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import mamba as mamba_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.attention import is_windowed
from repro.models.config import ModelConfig, LayerSpec


def attn_cache_len(cfg: ModelConfig, mixer: str, max_len: int) -> int:
    if is_windowed(mixer) and cfg.sliding_window > 0:
        return min(cfg.sliding_window, max_len)
    return max_len


def init_layer_cache(
    cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int, dtype
):
    if spec.mixer.startswith("attn"):
        L = attn_cache_len(cfg, spec.mixer, max_len)
        hd = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((batch, L, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, L, cfg.num_kv_heads, hd), dtype),
            "pos": jnp.full((batch, L), -1, jnp.int32),
        }
    if spec.mixer == "mamba":
        return mamba_mod.init_mamba_state(cfg, batch, dtype)
    if spec.mixer == "rwkv6":
        return rwkv_mod.init_rwkv_state(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    unit = tuple(
        init_layer_cache(cfg, spec, batch, max_len, dtype) for spec in cfg.unit
    )
    # the broadcast already makes a fresh buffer: a copy of it would hold
    # the whole cache twice while it is made
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.num_units,) + x.shape)
        if cfg.num_units
        else x,
        unit,
    )
    tail = tuple(
        init_layer_cache(cfg, spec, batch, max_len, dtype) for spec in cfg.tail
    )
    return {"units": stacked, "tail": tail}


def insert_slot_cache(cache: dict, slot_cache: dict, b) -> dict:
    """Write a batch-1 cache (one request, e.g. fresh from prefill) into batch
    row ``b`` of a batched decode cache.

    This is the continuous-batching admission primitive: a finished slot's
    rows are overwritten in place by the next request's prefilled KV state,
    with no barrier on the other slots.  ``b`` may be a traced int32 scalar,
    so one jitted insert serves every slot.  Unit leaves carry the stacked
    ``(num_units, B, ...)`` layout (batch axis 1); tail leaves are plain
    ``(B, ...)`` (batch axis 0).
    """

    def ins(axis):
        def f(big, small):
            return jax.lax.dynamic_update_slice_in_dim(
                big, small.astype(big.dtype), b, axis
            )
        return f

    return {
        "units": jax.tree.map(ins(1), cache["units"], slot_cache["units"]),
        "tail": jax.tree.map(ins(0), cache["tail"], slot_cache["tail"]),
    }
