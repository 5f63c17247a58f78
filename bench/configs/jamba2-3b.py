"""Plain reference of AI21-Jamba2-3B (huggingface.co/ai21labs/AI21-Jamba2-3B,
``config.json``, model_type ``jamba``) as the served model, its weights from
the seed, and its operation and byte counts.

A pre-norm decoder of ``num_hidden_layers`` layers: token embedding; per
layer an RMSNorm, then the mixer, a residual add, a second RMSNorm, a SwiGLU
MLP (silu(x Wg) * (x Wu)) Wd and a residual add; a final RMSNorm and logits
against the tied embedding.  Layer i mixes with attention where
``i % attn_layer_period == attn_layer_offset`` and with Mamba-1 elsewhere
(``num_experts`` is 1, so every MLP is dense).

* Attention: causal grouped-query attention, ``num_attention_heads`` query
  heads of size hidden/heads over ``num_key_value_heads`` key/value heads,
  scaled by 1/sqrt(head size), no positional encoding, no biases.
* Mamba-1, token by token: u, z = x W_in; a causal depthwise convolution of
  width ``mamba_d_conv`` with bias over u, then silu; dt, B, C = u W_x, each
  through an RMSNorm with its own scale (Jamba); dt = softplus(dt W_dt +
  b_dt); with A = -exp(A_log), per channel c and state n
  h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t and y_t = C_t . h_t + D u_t; the
  output (y * silu(z)) W_out.  No projection biases.

The weights follow the layout the serving program reads: ``embed`` (V, D),
``units`` one period of layers whose leaves carry a leading axis over the
periods, an empty ``tail`` and ``final_norm``.  Everything is float32 and
the matmuls run at the precision the caller sets (the benchmark: highest).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

DTYPE_BYTES = 4   # float32, as the configuration states


def period(c: Dict[str, Any]) -> int:
    return math.lcm(c["attn_layer_period"], c["expert_layer_period"])


def kinds(c: Dict[str, Any]) -> List[str]:
    """The mixer of each layer of one period: "attn" or "mamba"."""
    return ["attn" if i % c["attn_layer_period"] == c["attn_layer_offset"]
            else "mamba" for i in range(period(c))]


def _sizes(c):
    D = c["hidden_size"]
    din = c["mamba_expand"] * D
    return (D, din, c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"],
            c["num_attention_heads"], c["num_key_value_heads"],
            D // c["num_attention_heads"])


def make_weights(seed_key, c: Dict[str, Any]):
    """Every weight from the seed, on the device in one jitted call:
    embedding N(0, 0.02); each projection, the convolution's weights and
    bias N(0, 1/fan_in); A_log = log(1..d_state) in every channel; dt's
    bias softplus^-1(0.01); D and every norm's scale 1."""
    D, din, ds, dc, dtr, H, Kv, hd = _sizes(c)
    F, V = c["intermediate_size"], c["vocab_size"]
    U = c["num_hidden_layers"] // period(c)
    proj = {"attn": {"wq": (D, H * hd), "wk": (D, Kv * hd),
                     "wv": (D, Kv * hd), "wo": (H * hd, D)},
            "mamba": {"in_proj": (D, 2 * din), "x_proj": (din, dtr + 2 * ds),
                      "dt_proj": (dtr, din), "out_proj": (din, D),
                      "conv_w": (dc, din)}}
    mlp = {"gate": (D, F), "up": (D, F), "down": (F, D)}

    def normal(key, fan_in, shape):
        return jax.random.normal(key, (U,) + shape, jnp.float32) / math.sqrt(
            fan_in)

    def make(key):
        k_embed, *k_layers = jax.random.split(key, period(c) + 1)
        layers = []
        for kind, k in zip(kinds(c), k_layers):
            ks = iter(jax.random.split(k, 16))
            mixer = {n: normal(next(ks), s[0], s)
                     for n, s in sorted(proj[kind].items())}
            if kind == "mamba":
                mixer["conv_b"] = normal(next(ks), dc, (din,))
                mixer["dt_bias"] = jnp.full((U, din), math.log(math.expm1(0.01)),
                                            jnp.float32)
                mixer["A_log"] = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32)),
                    (U, din, ds))
                ones = lambda n: jnp.ones((U, n), jnp.float32)
                mixer.update(D=ones(din), dt_norm=ones(dtr), b_norm=ones(ds),
                             c_norm=ones(ds))
            layers.append({
                "norm1": {"scale": jnp.ones((U, D), jnp.float32)},
                "mixer": mixer,
                "norm2": {"scale": jnp.ones((U, D), jnp.float32)},
                "mlp": {n: normal(next(ks), s[0], s)
                        for n, s in sorted(mlp.items())}})
        return {"embed": jax.random.normal(k_embed, (V, D), jnp.float32) * 0.02,
                "units": tuple(layers), "tail": (),
                "final_norm": {"scale": jnp.ones((D,), jnp.float32)}}

    return jax.jit(make)(seed_key)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _attention(p, h, c):
    """Causal GQA over h (S, D), one query head at a time."""
    S = h.shape[0]
    _, _, _, _, _, H, Kv, hd = _sizes(c)
    q = (h @ p["wq"]).reshape(S, H, hd)
    k = (h @ p["wk"]).reshape(S, Kv, hd)
    v = (h @ p["wv"]).reshape(S, Kv, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = jnp.asarray(1.0 / math.sqrt(hd), h.dtype)

    def head(args):
        qh, kh, vh = args
        s = jnp.where(causal, (qh @ kh.T) * scale,
                      jnp.asarray(-1e30, jnp.float32).astype(h.dtype))
        return jax.nn.softmax(s, axis=-1) @ vh

    group = H // Kv
    kk = jnp.repeat(k, group, axis=1).transpose(1, 0, 2)
    vv = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)
    o = jax.lax.map(head, (q.transpose(1, 0, 2), kk, vv))    # (H, S, hd)
    return o.transpose(1, 0, 2).reshape(S, H * hd) @ p["wo"], k, v


def _mamba(p, h, c, length):
    """Mamba-1 over h (S, D), token by token.  Returns the output and the
    state after token ``length`` - 1: the convolution's last d_conv - 1
    inputs and the SSM state h (d_inner, d_state)."""
    S = h.shape[0]
    _, din, ds, dc, dtr, _, _, _ = _sizes(c)
    eps = c["rms_norm_eps"]
    u, z = jnp.split(h @ p["in_proj"], 2, axis=-1)
    u_pad = jnp.concatenate([jnp.zeros((dc - 1, din), u.dtype), u])
    conv = sum(u_pad[i:i + S] * p["conv_w"][i] for i in range(dc))
    ua = jax.nn.silu(conv + p["conv_b"])
    proj = ua @ p["x_proj"]
    dt = _rms(proj[:, :dtr], p["dt_norm"], eps)
    B = _rms(proj[:, dtr:dtr + ds], p["b_norm"], eps)
    C = _rms(proj[:, dtr + ds:], p["c_norm"], eps)
    dt = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(state, xs):
        t, u_t, dt_t, B_t, C_t = xs
        new = (jnp.exp(dt_t[:, None] * A) * state
               + (dt_t * u_t)[:, None] * B_t[None, :])
        return jnp.where(t < length, new, state), new @ C_t

    h_last, ys = jax.lax.scan(step, jnp.zeros((din, ds), h.dtype),
                              (jnp.arange(S), ua, dt, B, C), unroll=8)
    y = ys + ua * p["D"]
    conv_state = jax.lax.dynamic_slice_in_dim(u_pad, length, dc - 1)
    return (y * jax.nn.silu(z)) @ p["out_proj"], conv_state, h_last


def forward(w, tokens, c: Dict[str, Any], dtype=jnp.float32,
            return_kv: bool = False, return_state: bool = False,
            length=None):
    """tokens (S,) -> logits (S, V), computed in ``dtype``.  With
    ``return_kv`` also the attention layers' keys and values, each
    (attention layers, S, kv heads, head size); with ``return_state`` the
    Mamba layers' convolution states (layers, d_conv - 1, d_inner) and SSM
    states (layers, d_inner, d_state) after token ``length`` - 1 (default:
    the last).  Layers in order of depth."""
    S = tokens.shape[0]
    eps = c["rms_norm_eps"]
    length = S if length is None else length
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    x = w["embed"][tokens]
    ks = kinds(c)

    def unit(x, lp):
        kv, st = [], []
        for kind, p in zip(ks, lp):
            h = _rms(x, p["norm1"]["scale"], eps)
            if kind == "attn":
                o, k, v = _attention(p["mixer"], h, c)
                kv.append((k, v))
            else:
                o, conv, ssm = _mamba(p["mixer"], h, c, length)
                st.append((conv, ssm))
            x = x + o
            h = _rms(x, p["norm2"]["scale"], eps)
            m = p["mlp"]
            x = x + (jax.nn.silu(h @ m["gate"]) * (h @ m["up"])) @ m["down"]
        return x, (kv, st)

    x, (kv, st) = jax.lax.scan(unit, x, w["units"])
    logits = _rms(x, w["final_norm"]["scale"], eps) @ w["embed"].T
    out = (logits,)

    def layers(parts):
        # (periods, layers of a kind per period, ...) in order of depth
        a = jnp.stack(parts, axis=1)
        return a.reshape((-1,) + a.shape[2:])

    if return_kv:
        out += (layers([k for k, _ in kv]), layers([v for _, v in kv]))
    if return_state:
        out += (layers([s for s, _ in st]), layers([s for _, s in st]))
    return out if len(out) > 1 else logits


# ----------------------------------------------------------------------
# operation and byte counts, from the shapes
# ----------------------------------------------------------------------
def layer_counts(c: Dict[str, Any]):
    """(attention layers, Mamba layers) over the whole depth."""
    per = kinds(c)
    n = c["num_hidden_layers"] // period(c)
    return n * per.count("attn"), n * per.count("mamba")


def matmul_params(c: Dict[str, Any], kind: str) -> int:
    """Weights of one layer that multiply each token's activations."""
    D, din, ds, dc, dtr, H, Kv, hd = _sizes(c)
    mlp = 3 * D * c["intermediate_size"]
    if kind == "attn":
        return 2 * D * H * hd + 2 * D * Kv * hd + mlp
    return D * 2 * din + din * (dtr + 2 * ds) + dtr * din + din * D + mlp


def params(c: Dict[str, Any]) -> int:
    D, din, ds, dc, dtr, _, _, _ = _sizes(c)
    n_attn, n_mamba = layer_counts(c)
    mamba_rest = dc * din + din + din + din * ds + din + dtr + 2 * ds
    norms = 2 * D
    return (n_attn * (matmul_params(c, "attn") + norms)
            + n_mamba * (matmul_params(c, "mamba") + mamba_rest + norms)
            + c["vocab_size"] * D + D)


def state_bytes_per_slot(c: Dict[str, Any]) -> int:
    """Recurrent state of one request: every Mamba layer's convolution
    inputs and SSM state."""
    _, din, ds, dc, _, _, _, _ = _sizes(c)
    return DTYPE_BYTES * layer_counts(c)[1] * ((dc - 1) * din + din * ds)


def weight_bytes(c: Dict[str, Any]) -> int:
    """Bytes one decode tick has to move whatever the contexts: all weights,
    and every slot's recurrent state, read and written (the slot cache's
    keys and values are counted per position in use)."""
    slots = c["serving"]["num_slots"]
    return DTYPE_BYTES * params(c) + 2 * slots * state_bytes_per_slot(c)


def kv_bytes_per_position(c: Dict[str, Any]) -> int:
    """Keys and values of one position in every attention layer."""
    _, _, _, _, _, _, Kv, hd = _sizes(c)
    return DTYPE_BYTES * 2 * layer_counts(c)[0] * Kv * hd


def scan_ops_per_token(c: Dict[str, Any]) -> int:
    """One token's step of one Mamba layer outside the matmuls: the
    convolution (2 d_conv d_inner), exp(dt A) (2 d_inner d_state, an exp
    counted as one), dt B u (d_inner + d_inner d_state), the update
    (2 d_inner d_state), C . h (2 d_inner d_state) and D u with the gate
    (3 d_inner)."""
    _, din, ds, dc, _, _, _, _ = _sizes(c)
    return 2 * dc * din + 7 * din * ds + 4 * din


def prefill_flops(c: Dict[str, Any], S: int) -> int:
    """A prompt of S tokens: every layer's matmuls over every token, the
    scan's per-token operations, causal attention (scores and values over
    the S(S+1)/2 pairs) and the logits of the last token, which is all that
    prefill computes."""
    D, _, _, _, _, H, _, hd = _sizes(c)
    n_attn, n_mamba = layer_counts(c)
    pairs = S * (S + 1) // 2
    return (n_attn * (2 * matmul_params(c, "attn") * S + 4 * H * hd * pairs)
            + n_mamba * (2 * matmul_params(c, "mamba") + scan_ops_per_token(c))
            * S + 2 * D * c["vocab_size"])


def decode_flops(c: Dict[str, Any], context: int) -> int:
    """One generated token whose attention layers attend over ``context``
    positions."""
    D, _, _, _, _, H, _, hd = _sizes(c)
    n_attn, n_mamba = layer_counts(c)
    return (n_attn * (2 * matmul_params(c, "attn") + 4 * H * hd * context)
            + n_mamba * (2 * matmul_params(c, "mamba") + scan_ops_per_token(c))
            + 2 * D * c["vocab_size"])
