"""The ragged decode-attention kernel (``kernels/decode_attention.py``) in
interpret mode against ``_dense_attention`` over the same cache.

Each case is a stacked cache ``(U, B, Sc, Kv, Dh)`` read at a traced unit
index, with ragged slot positions: a free slot (-1), a slot at the last
row ``Sc - 1``, and rows holding -1 inside and past each live range.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels.decode_attention import ragged_decode_attention_kernel
from repro.models.attention import _dense_attention, _pair_mask, ragged_block

# (U, B, Sc, Kv, G, Dh, block, positions of the B slots)
CASES = {
    "G1-Kv4": (3, 4, 256, 4, 1, 128, 128, [5, -1, 255, 130]),
    "G1-Kv2-narrow-heads": (2, 3, 256, 2, 1, 32, 128, [100, 255, -1]),
    "G20-Kv1": (2, 3, 256, 1, 20, 16, 128, [0, 200, -1]),
    "G2-Kv2-one-block": (2, 3, 48, 2, 2, 30, 48, [47, -1, 3]),
    "G4-Kv2-block256": (2, 2, 512, 2, 4, 16, 256, [300, 511]),
    "all-free": (2, 2, 256, 2, 1, 16, 128, [-1, -1]),
}


def _cache(U, B, Sc, q_pos, rng):
    """Row r of a live slot holds position r up to the slot's position,
    then -1; about one row in eight of each range is -1 as well (never the
    slot's own row)."""
    r = np.arange(Sc)[None]
    q = np.asarray(q_pos)[:, None]
    pos = np.where(r <= q, r, -1)
    holes = (rng.random((U, B, Sc)) < 0.125) & (r != q)
    return np.where(holes, -1, pos[None]).astype(np.int32)


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_dense_attention(case):
    U, B, Sc, Kv, G, Dh, block, q_pos = CASES[case]
    H = Kv * G
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, 1, H, Dh), jnp.float32)
    k = jax.random.normal(keys[1], (U, B, Sc, Kv, Dh), jnp.float32)
    v = jax.random.normal(keys[2], (U, B, Sc, Kv, Dh), jnp.float32)
    pos = jnp.asarray(_cache(U, B, Sc, q_pos, rng))
    q_pos = jnp.asarray(q_pos, jnp.int32)

    @jax.jit
    def kernel(unit):
        return ragged_decode_attention_kernel(
            q[:, 0] * Dh ** -0.5, k, v, pos, q_pos, unit, block=block,
            interpret=True)

    live = np.asarray(q_pos) >= 0
    for unit in range(U):
        got = np.asarray(kernel(jnp.int32(unit)))
        mask = _pair_mask(q_pos[:, None], pos[unit], causal=True, window=0)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(
                _dense_attention(q, k[unit], v[unit], mask, 0.0))[:, 0]
        if live.any():
            scale = np.abs(want[live]).max()
            np.testing.assert_allclose(got[live], want[live], rtol=1e-6,
                                       atol=1e-6 * scale)
        assert not got[~live].any(), "a free slot's row is zero"


@pytest.mark.parametrize("arch,mixer,cache_len,devices,block", [
    ("olmo-1b", "attn", 1024, 1, 256),
    ("olmo-1b", "attn", 384, 1, 128),
    ("olmo-1b", "attn", 48, 1, 48),          # one block of a short cache
    ("olmo-1b", "attn", 1024, 8, 0),         # a cache sharded over a mesh
    ("gemma3-4b", "attn_global", 4096, 1, 256),
    ("gemma3-4b", "attn_local", 1024, 1, 0),  # a ring buffer
    ("hubert-xlarge", "attn", 1024, 1, 0),   # bidirectional
    ("jamba2-3b", "attn", 4352, 1, 0),       # beside mamba layers
], ids=lambda v: str(v))
def test_ragged_block_picks_the_path(arch, mixer, cache_len, devices, block):
    """Which decode layers the kernel reads, in blocks of how many rows, and
    which attend densely over every row."""
    mesh = type("Mesh", (), {"size": devices})()
    assert ragged_block(registry.get_config(arch), mixer, cache_len,
                        mesh) == block
