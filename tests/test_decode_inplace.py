"""The decode scan carries the stacked unit cache and writes each layer's new
rows or state into it at the unit's index.  Held bit for bit against a plain
reference: each unit's cache sliced out of the stack, its layers stepped in
their plain ``(B, ...)`` form in a Python loop, and the results restacked.

Slots sit at mixed positions; gemma3's sliding-window layers hold a ring
buffer of 16 positions, and one slot's position crosses it during the run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import decode_step, init_cache, init_model
from repro.models.config import LayerSpec
from repro.models.transformer import apply_layer_decode, lm_logits

B, MAX_LEN, TICKS = 4, 48, 6
START = np.array([3, 14, 30, 40], np.int32)   # slot 1 wraps the window of 16


def _config(arch):
    cfg = registry.smoke_config(arch)
    if arch == "gemma3-4b":
        # two units, so the carry's index moves, and a tail layer outside
        # the scan
        cfg = cfg.replace(num_units=2,
                          tail=(LayerSpec(mixer="attn_local", mlp="dense"),))
    return cfg


def _filled_cache(cfg):
    """Random keys, values and states; each attention layer's positions
    as a ring of its length holds them after ``START`` tokens."""
    cache = init_cache(cfg, B, MAX_LEN, jnp.float32)
    paths, tdef = jax.tree_util.tree_flatten_with_path(cache)
    keys = jax.random.split(jax.random.PRNGKey(2), len(paths))
    leaves = []
    for key, (path, leaf) in zip(keys, paths):
        if getattr(path[-1], "key", None) == "pos":
            sc = leaf.shape[-1]
            s = np.arange(sc)[None, :]
            last = START[:, None] - 1
            pos = s + sc * ((last - s) // sc)
            pos = np.where(last >= s, pos, -1).astype(np.int32)
            leaves.append(jnp.broadcast_to(jnp.asarray(pos), leaf.shape))
        else:
            leaves.append(jax.random.normal(key, leaf.shape, leaf.dtype))
    return jax.tree.unflatten(tdef, leaves)


def _reference_step(params, cfg, tokens, position, cache):
    """The stack as the scan's inputs and outputs: each unit's cache is
    sliced out of the stack, its layers step their plain per-layer caches
    one after another, and the new caches are restacked.  (A scan and not
    a Python loop over units: XLA's CPU backend fuses an unrolled stack of
    gemma3's layers differently and moves its logits by about 1e-6.)"""
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    def unit(x, scanned):
        unit_params, unit_cache = scanned
        new = []
        for i, spec in enumerate(cfg.unit):
            x, c = apply_layer_decode(unit_params[i], spec, x, position,
                                      unit_cache[i], cfg, None, None)
            new.append(c)
        return x, tuple(new)

    x, stacked = jax.lax.scan(unit, x, (params["units"], cache["units"]))
    tail = []
    for i, spec in enumerate(cfg.tail):
        x, c = apply_layer_decode(params["tail"][i], spec, x, position,
                                  cache["tail"][i], cfg, None, None)
        tail.append(c)
    return lm_logits(params, cfg, x), {"units": stacked, "tail": tuple(tail)}


@pytest.mark.parametrize("arch",
                         ["olmo-1b", "jamba2-3b", "gemma3-4b", "rwkv6-7b"])
def test_carried_cache_matches_plain_reference(arch):
    cfg = _config(arch)
    params = init_model(jax.random.PRNGKey(1), cfg)
    carried = jax.jit(lambda p, t, q, c: decode_step(p, cfg, t, q, c))
    plain = jax.jit(lambda p, t, q, c: _reference_step(p, cfg, t, q, c))
    tokens = jnp.arange(1, B + 1, dtype=jnp.int32)[:, None]
    position = jnp.asarray(START)
    cache_a = cache_b = _filled_cache(cfg)
    for _ in range(TICKS):
        logits_a, cache_a = carried(params, tokens, position, cache_a)
        logits_b, cache_b = plain(params, tokens, position, cache_b)
        np.testing.assert_array_equal(np.asarray(logits_a),
                                      np.asarray(logits_b))
        assert (jax.tree.structure(cache_a)
                == jax.tree.structure(cache_b))
        for a, b in zip(jax.tree.leaves(cache_a), jax.tree.leaves(cache_b)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        tokens = jnp.argmax(logits_a[:, -1], -1).astype(jnp.int32)[:, None]
        position = position + 1
    if arch == "gemma3-4b":
        # the window's ring wrapped for slot 1: 14 + 6 positions in 16 rows
        ring = np.asarray(cache_a["units"][0]["pos"][0, 1])
        assert ring.shape == (16,) and ring.max() == 19 and ring.min() == 4
