"""Plain reference of OLMo-1B (arXiv:2402.00838) as the served model, its
weights from the seed, and its operation and byte counts.

A pre-norm decoder: token embedding; per layer a non-parametric LayerNorm
(no scale, no bias), causal multi-head attention with rotary position
embedding on the two halves of each head (theta 10000), a second
non-parametric LayerNorm and a SwiGLU MLP (silu(x Wg) * (x Wu)) Wd; a final
non-parametric LayerNorm and logits against the tied embedding.  No biases.
The LayerNorm epsilon is the one the configuration file states.

The weights follow the layout the serving program reads: ``embed`` (V, D),
``units`` a one-layer unit whose leaves carry a leading layer axis
(``mixer`` wq, wk, wv, wo; ``mlp`` gate, up, down; empty ``norm1`` and
``norm2``), an empty ``tail`` and ``final_norm``.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

DTYPE_BYTES = 4   # float32, as the configuration states


def make_weights(seed_key, c: Dict[str, Any]):
    """Every weight from the seed, on the device in one jitted call:
    embedding N(0, 0.02), each projection N(0, 1/fan_in)."""
    L, D, H, F, V = (c["num_hidden_layers"], c["hidden_size"],
                     c["num_attention_heads"], c["intermediate_size"],
                     c["vocab_size"])
    hd = D // H
    shapes = {"wq": (D, H * hd), "wk": (D, H * hd), "wv": (D, H * hd),
              "wo": (H * hd, D), "gate": (D, F), "up": (D, F), "down": (F, D)}

    def make(key):
        keys = jax.random.split(key, len(shapes) + 1)
        leaf = {}
        for k, (name, (fi, fo)) in zip(keys[1:], sorted(shapes.items())):
            leaf[name] = (jax.random.normal(k, (L, fi, fo), jnp.float32)
                          / math.sqrt(fi))
        layer = {"norm1": {}, "norm2": {},
                 "mixer": {n: leaf[n] for n in ("wq", "wk", "wv", "wo")},
                 "mlp": {n: leaf[n] for n in ("gate", "up", "down")}}
        return {"embed": jax.random.normal(keys[0], (V, D), jnp.float32) * 0.02,
                "units": (layer,), "tail": (), "final_norm": {}}

    return jax.jit(make)(seed_key)


def _ln(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x (S, H, hd): rotate the first half against the second."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def forward(w, tokens, c: Dict[str, Any], dtype=jnp.float32,
            return_kv: bool = False):
    """tokens (S,) -> logits (S, V), computed in ``dtype``; with
    ``return_kv`` also every layer's keys (rotated) and values, each
    (layers, S, heads, head size)."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    hd, eps, theta = D // H, c["layer_norm_eps"], c["rope_theta"]
    S = tokens.shape[0]
    cast = lambda a: a.astype(dtype)
    x = cast(w["embed"])[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = _ln(x, eps)
        q = _rope((h @ cast(p["mixer"]["wq"])).reshape(S, H, hd), theta)
        k = _rope((h @ cast(p["mixer"]["wk"])).reshape(S, H, hd), theta)
        v = (h @ cast(p["mixer"]["wv"])).reshape(S, H, hd)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.asarray(math.sqrt(hd), dtype)
        s = jnp.where(causal[None], s, jnp.asarray(-1e30, jnp.float32).astype(dtype))
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v).reshape(S, D)
        x = x + o @ cast(p["mixer"]["wo"])
        h = _ln(x, eps)
        m = jax.nn.silu(h @ cast(p["mlp"]["gate"])) * (h @ cast(p["mlp"]["up"]))
        return x + m @ cast(p["mlp"]["down"]), ((k, v) if return_kv else None)

    x, kv = jax.lax.scan(layer, x, w["units"][0])
    logits = _ln(x, eps) @ cast(w["embed"]).T
    return (logits, *kv) if return_kv else logits


# ----------------------------------------------------------------------
# operation and byte counts, from the shapes
# ----------------------------------------------------------------------
def layer_params(c: Dict[str, Any]) -> int:
    D, F = c["hidden_size"], c["intermediate_size"]
    return 4 * D * D + 3 * D * F


def weight_bytes(c: Dict[str, Any]) -> int:
    """Bytes of all weights: what one decode step has to read."""
    return DTYPE_BYTES * (c["num_hidden_layers"] * layer_params(c)
                          + c["vocab_size"] * c["hidden_size"])


def kv_bytes_per_position(c: Dict[str, Any]) -> int:
    """Keys and values of one position in every layer."""
    return DTYPE_BYTES * 2 * c["num_hidden_layers"] * c["hidden_size"]


def prefill_flops(c: Dict[str, Any], S: int) -> int:
    """A prompt of S tokens: the layers over every token, causal attention
    (scores and values over the S(S+1)/2 pairs) and the logits of the last
    token, which is all that prefill computes."""
    L, D, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    pairs = S * (S + 1) // 2
    return L * (2 * layer_params(c) * S + 4 * D * pairs) + 2 * D * V


def decode_flops(c: Dict[str, Any], context: int) -> int:
    """One generated token that attends over ``context`` positions."""
    L, D, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    return L * (2 * layer_params(c) + 4 * D * context) + 2 * D * V
