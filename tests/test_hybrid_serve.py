"""ServeEngine on a smoke-sized Jamba2 (units of Mamba, Mamba, attention,
Mamba; two units) against the benchmark's plain reference
(``bench/configs/jamba2-3b.py``): prefill at prompt lengths that are not a
multiple of the scan's chunk, then decode through the slot cache.  The
served tokens are the reference's best, and the slot's keys, values and
recurrent state equal the reference's after the same tokens."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.serve import ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import harness  # noqa: E402

MAX_LEN = 128
TICKS = 6


@pytest.fixture(scope="module")
def setup():
    cfg = registry.smoke_config("jamba2-3b")
    model = harness.load_module("configs", "jamba2-3b", REPO)
    config = {"attn_layer_period": 4, "attn_layer_offset": 2,
              "expert_layer_period": 2, "expert_layer_offset": 1,
              "hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
              "num_attention_heads": cfg.num_heads,
              "num_key_value_heads": cfg.num_kv_heads,
              "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
              "mamba_expand": cfg.mamba_expand,
              "mamba_d_state": cfg.mamba_d_state,
              "mamba_d_conv": cfg.mamba_d_conv,
              "mamba_dt_rank": cfg.resolved_dt_rank,
              "rms_norm_eps": cfg.norm_eps}
    assert [s.mixer for s in cfg.unit] == model.kinds(config)
    weights = model.make_weights(jax.random.PRNGKey(0), config)
    eng = ServeEngine(cfg, weights, num_slots=2, max_len=MAX_LEN)
    return cfg, model, config, weights, eng


def _by_depth(units, at, key, b):
    a = jnp.stack([units[i][key][:, b] for i in at], axis=1)
    return a.reshape((-1,) + a.shape[2:])


@pytest.mark.parametrize("S", [37, 100])
def test_engine_prefill_then_decode_matches_reference(setup, S):
    cfg, model, config, weights, eng = setup
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(S), (S,), 0,
                                           cfg.vocab_size), np.int32)
    tok, slot_cache = eng._prefill(weights, eng._make_prompt_batch(prompt))
    tokens, positions, cache = eng._fresh_state()
    b = 1
    tokens, positions, cache = eng._insert(
        cache, tokens, positions, slot_cache, tok, jnp.asarray(S, jnp.int32),
        jnp.asarray(b, jnp.int32))
    served = [int(tok[0, 0])]
    for _ in range(TICKS):
        tokens, positions, cache = eng._tick(weights, tokens, positions,
                                             cache)
        served.append(int(tokens[b, 0]))

    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = len(seq)
    padded = np.zeros(MAX_LEN, np.int32)
    padded[:n] = seq
    logits, k_ref, v_ref, conv_ref, ssm_ref = model.forward(
        weights, jnp.asarray(padded), config, return_kv=True,
        return_state=True, length=n)
    # every served token is the reference's best at its position
    rows = logits[S - 1 + np.arange(len(served))]
    got = jnp.take_along_axis(rows, jnp.asarray(served)[:, None], axis=-1)
    assert float(jnp.max(rows.max(axis=-1) - got[:, 0])) < 1e-4

    units = cache["units"]
    attn_at = [i for i, s in enumerate(cfg.unit) if s.mixer == "attn"]
    mamba_at = [i for i, s in enumerate(cfg.unit) if s.mixer == "mamba"]
    for key, ref in (("k", k_ref), ("v", v_ref)):
        np.testing.assert_allclose(
            np.asarray(_by_depth(units, attn_at, key, b))[:, :n],
            np.asarray(ref)[:, :n], atol=1e-5, rtol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(units[attn_at[0]]["pos"][0, b])[:n], np.arange(n))
    for key, ref in (("conv", conv_ref), ("ssm", ssm_ref)):
        np.testing.assert_allclose(
            np.asarray(_by_depth(units, mamba_at, key, b)), np.asarray(ref),
            atol=1e-5, rtol=1e-4)
    # the prefilled state alone equals the reference's after the prompt
    _, _, conv_p, ssm_p = model.forward(
        weights, jnp.asarray(padded), config, return_kv=True,
        return_state=True, length=S)[1:]
    np.testing.assert_allclose(
        np.asarray(_by_depth(slot_cache["units"], mamba_at, "ssm", 0)),
        np.asarray(ssm_p), atol=1e-5, rtol=1e-4)
