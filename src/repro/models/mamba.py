"""Mamba-1 selective SSM block (as used inside Jamba).

Training/prefill runs a *chunked* scan: an outer ``lax.scan`` over chunks of
``CHUNK`` tokens (rematerialized, so backward keeps only per-chunk states)
with an inner exact sequential scan, under the ``mamba.scan`` named scope.
A sequence that is not a multiple of ``CHUNK`` is padded with steps that
carry the state unchanged.  Decode is the exact single-step
recurrence with a (conv_state, ssm_state) cache.

Recurrence (per channel c of d_inner, per state dim n of d_state):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * u_t
    y_t = C_t . h_t + D_param * u_t
with input-dependent dt (softplus), B, C (Jamba applies RMSNorm to dt/B/C
before projection).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init

CHUNK = 64


def init_mamba(key, cfg: ModelConfig, dtype) -> dict:
    D = cfg.d_model
    din = cfg.mamba_d_inner
    ds = cfg.mamba_d_state
    dc = cfg.mamba_d_conv
    dtr = cfg.resolved_dt_rank
    ks = jax.random.split(key, 6)
    # S4D-real initialization for A
    a = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (din, 1))
    dt_init_std = dtr ** -0.5
    return {
        "in_proj": dense_init(ks[0], D, 2 * din, dtype=dtype),
        "conv_w": (
            jax.random.normal(ks[1], (dc, din)) / math.sqrt(dc)
        ).astype(dtype),
        "conv_b": jnp.zeros((din,), dtype),
        "x_proj": dense_init(ks[2], din, dtr + 2 * ds, dtype=dtype),
        "dt_proj": (
            jax.random.uniform(ks[3], (dtr, din), minval=-dt_init_std,
                               maxval=dt_init_std)
        ).astype(dtype),
        "dt_bias": jnp.full((din,), -4.6, dtype),  # softplus^-1(0.01)
        "A_log": jnp.log(a).astype(jnp.float32),
        "D": jnp.ones((din,), dtype),
        "out_proj": dense_init(ks[4], din, D, dtype=dtype),
        # Jamba-style RMSNorms on dt / B / C
        "dt_norm": jnp.ones((dtr,), dtype),
        "b_norm": jnp.ones((ds,), dtype),
        "c_norm": jnp.ones((ds,), dtype),
    }


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(
        x.dtype
    )


def _ssm_inputs(params, u, cfg: ModelConfig):
    """u: (B,S,din) post-conv activations -> (dt, Bmat, Cmat) in f32."""
    ds = cfg.mamba_d_state
    dtr = cfg.resolved_dt_rank
    proj = u @ params["x_proj"]                            # (B,S,dtr+2ds)
    dt_lowrank = _rms(proj[..., :dtr], params["dt_norm"])
    Bmat = _rms(proj[..., dtr : dtr + ds], params["b_norm"]).astype(jnp.float32)
    Cmat = _rms(proj[..., dtr + ds :], params["c_norm"]).astype(jnp.float32)
    dt = jax.nn.softplus(
        (dt_lowrank @ params["dt_proj"]).astype(jnp.float32)
        + params["dt_bias"].astype(jnp.float32)
    )                                                      # (B,S,din)
    return dt, Bmat, Cmat


def _ssm_step(h, inp, A):
    """h: (B,din,ds); inp = (u_t (B,din), dt_t (B,din), B_t (B,ds), C_t (B,ds))."""
    u_t, dt_t, B_t, C_t = inp
    da = jnp.exp(dt_t[..., None] * A[None])                # (B,din,ds)
    dbu = (dt_t * u_t)[..., None] * B_t[:, None, :]        # (B,din,ds)
    h = da * h + dbu
    y = jnp.einsum("bdn,bn->bd", h, C_t)
    return h, y


def _scan_chunk(params_A, h0, u, dt, Bm, Cm):
    """Exact inner scan over a chunk.  u,dt: (B,L,din); Bm,Cm: (B,L,ds)."""
    def step(h, xs):
        return _ssm_step(h, xs, params_A)

    xs = (
        u.swapaxes(0, 1),
        dt.swapaxes(0, 1),
        Bm.swapaxes(0, 1),
        Cm.swapaxes(0, 1),
    )
    h, ys = jax.lax.scan(step, h0, xs)
    return h, ys.swapaxes(0, 1)                            # (B,L,din)


def _time_pad(t, pad: int):
    """(B,S,C) -> (B,S+pad,C) with zero steps after the sequence.  A pad step
    has dt = 0, so exp(dt*A) = 1 and dt*B*u = 0 carry the state through it
    unchanged: the state handed to decode is exact for any S.  (dt computed
    there, softplus(dt_bias), would decay it.)"""
    return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t


def mamba_forward(params, x, cfg: ModelConfig, state=None):
    """x: (B,S,D) -> (out, new_state).

    state: None or dict(conv (B,dc-1,din), ssm (B,din,ds))."""
    B, S, D = x.shape
    din = cfg.mamba_d_inner
    ds = cfg.mamba_d_state
    dc = cfg.mamba_d_conv

    xz = x @ params["in_proj"]
    u, z = jnp.split(xz, 2, axis=-1)                       # (B,S,din) each

    conv_prev = (
        state["conv"] if state else jnp.zeros((B, dc - 1, din), x.dtype)
    )
    ssm_prev = (
        state["ssm"] if state else jnp.zeros((B, din, ds), jnp.float32)
    )
    # causal depthwise conv over time
    u_pad = jnp.concatenate([conv_prev, u], axis=1)        # (B,S+dc-1,din)
    conv = sum(
        u_pad[:, i : i + S, :] * params["conv_w"][i][None, None]
        for i in range(dc)
    )
    u_act = jax.nn.silu(conv + params["conv_b"]).astype(jnp.float32)

    dt, Bm, Cm = _ssm_inputs(params, u_act.astype(x.dtype), cfg)
    A = -jnp.exp(params["A_log"])                          # (din,ds)

    pad = (-S) % CHUNK
    u_act_p, dt_p, Bm_p, Cm_p = (_time_pad(t, pad) for t in (u_act, dt, Bm, Cm))
    n = u_act_p.shape[1] // CHUNK

    reshape = lambda t: t.reshape(B, n, CHUNK, t.shape[-1]).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_body(h, xs):
        uc, dtc, bc, cc = xs
        return _scan_chunk(A, h, uc, dtc, bc, cc)

    with jax.named_scope("mamba.scan"):
        h_final, ys = jax.lax.scan(
            chunk_body,
            ssm_prev,
            (reshape(u_act_p), reshape(dt_p), reshape(Bm_p), reshape(Cm_p)),
        )
    y = ys.swapaxes(0, 1).reshape(B, n * CHUNK, din)[:, :S]
    y = y + u_act * params["D"].astype(jnp.float32)
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ params["out_proj"]

    new_state = {
        "conv": u_pad[:, S : S + dc - 1, :] if dc > 1 else conv_prev,
        "ssm": h_final,
    }
    return out, new_state


def mamba_step(params, x, cfg: ModelConfig, state):
    """Single-token decode.  x: (B,1,D)."""
    B, _, D = x.shape
    din = cfg.mamba_d_inner
    dc = cfg.mamba_d_conv

    xz = x[:, 0] @ params["in_proj"]
    u, z = jnp.split(xz, 2, axis=-1)                       # (B,din)

    conv_prev = state["conv"]                              # (B,dc-1,din)
    window = jnp.concatenate([conv_prev, u[:, None]], axis=1)  # (B,dc,din)
    conv = jnp.einsum("bcd,cd->bd", window, params["conv_w"])
    u_act = jax.nn.silu(conv + params["conv_b"]).astype(jnp.float32)

    dt, Bm, Cm = _ssm_inputs(params, u_act[:, None].astype(x.dtype), cfg)
    A = -jnp.exp(params["A_log"])
    with jax.named_scope("mamba.step"):
        h, y = _ssm_step(state["ssm"], (u_act, dt[:, 0], Bm[:, 0], Cm[:, 0]), A)
    y = y + u_act * params["D"].astype(jnp.float32)
    out = (y.astype(x.dtype) * jax.nn.silu(z))[:, None] @ params["out_proj"]
    return out, {"conv": window[:, 1:], "ssm": h}


def init_mamba_state(cfg: ModelConfig, batch: int, dtype) -> dict:
    return {
        "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.mamba_d_inner), dtype),
        "ssm": jnp.zeros(
            (batch, cfg.mamba_d_inner, cfg.mamba_d_state), jnp.float32
        ),
    }
