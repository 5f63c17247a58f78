"""Model-zoo behaviour: decode==forward consistency, chunked==dense attention,
flash gradients, M-RoPE, MoE dense path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (
    Batch, LayerSpec, ModelConfig, decode_step, forward, init_cache,
    init_model, prefill,
)
from repro.models.config import MLP_RWKV, dense_unit, moe_unit
from repro.models.frontends import hubert_batch, lm_batch, vlm_batch

KEY = jax.random.PRNGKey(0)


def tiny_dense(**kw):
    base = dict(
        name="t", arch_type="dense", d_model=64, vocab_size=97,
        unit=dense_unit(1), num_units=2, num_heads=4, num_kv_heads=2,
        d_ff=128,
    )
    base.update(kw)
    return ModelConfig(**base)


def decode_matches_forward(cfg, params, S=16, atol=5e-3):
    b = lm_batch(KEY, cfg, 2, S)
    _, cache = prefill(params, cfg, b, max_len=S + 8)
    tok = jnp.full((2, 1), 3, jnp.int32)
    pos = jnp.full((2,), S, jnp.int32)
    lg_dec, _ = decode_step(params, cfg, tok, pos, cache)
    ext = jnp.concatenate([b.tokens, tok], axis=1)
    b_ext = lm_batch(KEY, cfg, 2, S + 1)._replace(tokens=ext)
    lg_full, _ = forward(params, cfg, b_ext)
    return float(jnp.abs(lg_full[:, -1:] - lg_dec).max()) < atol


def test_dense_decode_consistency():
    cfg = tiny_dense()
    params = init_model(KEY, cfg)
    assert decode_matches_forward(cfg, params)


def test_swa_ring_buffer_decode():
    cfg = tiny_dense(unit=dense_unit(1, mixer="attn_swa"), sliding_window=8)
    params = init_model(KEY, cfg)
    assert decode_matches_forward(cfg, params, S=24)


def test_rwkv_decode_consistency():
    cfg = ModelConfig(
        name="r", arch_type="ssm", d_model=64, vocab_size=97,
        unit=(LayerSpec(mixer="rwkv6", mlp=MLP_RWKV),), num_units=2,
        d_ff=128, rwkv_head_dim=16, rwkv_lora_mix=8, rwkv_lora_decay=8,
    )
    params = init_model(KEY, cfg)
    assert decode_matches_forward(cfg, params, atol=5e-2)


def test_hybrid_decode_consistency():
    cfg = ModelConfig(
        name="j", arch_type="hybrid", d_model=64, vocab_size=97,
        unit=(LayerSpec(mixer="attn", mlp="dense"),
              LayerSpec(mixer="mamba", mlp="moe")),
        num_units=2, num_heads=4, num_kv_heads=2, d_ff=128,
        num_experts=4, num_experts_per_tok=2, mamba_d_state=8,
    )
    params = init_model(KEY, cfg)
    assert decode_matches_forward(cfg, params, atol=5e-2)


def _mamba_exact(p, x, cfg):
    """The recurrence token by token (``mamba_step``), from a zero state."""
    from repro.models import mamba

    state = mamba.init_mamba_state(cfg, x.shape[0], jnp.float32)
    outs = []
    for t in range(x.shape[1]):
        o, state = mamba.mamba_step(p, x[:, t:t + 1], cfg, state)
        outs.append(o)
    return jnp.concatenate(outs, axis=1), state


def _mamba_case():
    from repro.models import mamba

    cfg = ModelConfig(name="m", arch_type="hybrid", d_model=32,
                      vocab_size=97, unit=(LayerSpec(mixer="mamba"),),
                      num_units=1, d_ff=64, mamba_d_state=8)
    p = mamba.init_mamba(KEY, cfg, jnp.float32)
    # dt well away from 0, so a pad step that ran the recurrence would show
    p["dt_bias"] = jnp.full_like(p["dt_bias"], 0.5)
    return cfg, p


@pytest.mark.parametrize("S", [37, 100])
def test_mamba_forward_exact_off_chunk(S):
    """A prompt that is not a multiple of CHUNK: the chunked scan's output
    and the state it hands to decode equal the token-by-token recurrence."""
    from repro.models import mamba

    assert S % mamba.CHUNK
    cfg, p = _mamba_case()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, 32))
    out, state = mamba.mamba_forward(p, x, cfg)
    ref_out, ref_state = _mamba_exact(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-5)
    for k in ("conv", "ssm"):
        np.testing.assert_allclose(np.asarray(state[k]),
                                   np.asarray(ref_state[k]), atol=2e-6)


def test_mamba_pad_steps_that_run_the_recurrence_are_caught(monkeypatch):
    """The comparison above fails when the pad steps advance the state (the
    last step's inputs repeated on them)."""
    from repro.models import mamba

    monkeypatch.setattr(mamba, "_time_pad", lambda t, pad: jnp.pad(
        t, ((0, 0), (0, pad), (0, 0)), mode="edge") if pad else t)
    cfg, p = _mamba_case()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 37, 32))
    _, state = mamba.mamba_forward(p, x, cfg)
    _, ref_state = _mamba_exact(p, x, cfg)
    assert float(jnp.abs(state["ssm"] - ref_state["ssm"]).max()) > 1e-3


def test_chunked_attention_equals_dense():
    from repro.models import attention as attn

    cfg = tiny_dense()
    params = init_model(KEY, cfg)
    b = lm_batch(KEY, cfg, 2, 2048)
    ref, _ = forward(params, cfg, b)
    old = attn.DENSE_MAX
    try:
        attn.DENSE_MAX = 256
        out, _ = forward(params, cfg, b)
    finally:
        attn.DENSE_MAX = old
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=5e-4)


def test_flash_path_pads_off_block_lengths():
    """A prompt longer than DENSE_MAX that is no multiple of the flash
    blocks (a 4000-token prefill): padded keys are masked, pad rows dropped;
    one KV head under four query heads, no RoPE, as Jamba's attention."""
    from repro.models import attention as attn

    cfg = tiny_dense(num_kv_heads=1, rope="none")
    params = init_model(KEY, cfg)
    b = lm_batch(KEY, cfg, 1, 600)
    ref, _ = forward(params, cfg, b)
    old = attn.DENSE_MAX
    try:
        attn.DENSE_MAX = 256
        out, _ = forward(params, cfg, b)
    finally:
        attn.DENSE_MAX = old
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=5e-4)


def test_flash_gradients_match_dense():
    from repro.models.attention import _dense_attention, _pair_mask
    from repro.models.flash import flash_attention

    B, S, H, Kv, Dh = 2, 1024, 4, 2, 16
    q = jax.random.normal(KEY, (B, S, H, Dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Kv, Dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kv, Dh))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ct = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, Dh))
    for causal, window in ((True, 0), (True, 64), (False, 0)):
        mask = _pair_mask(pos, pos, causal=causal, window=window)
        g_ref = jax.grad(
            lambda *xs: (_dense_attention(*xs, mask, 0.0) * ct).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_fl = jax.grad(
            lambda *xs: (flash_attention(*xs, pos, pos, causal, window) * ct).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_mrope_reduces_to_rope_on_text():
    cfg = tiny_dense(rope="mrope", mrope_sections=(2, 3, 3), frontend="vision",
                     arch_type="vlm")
    params = init_model(KEY, cfg)
    bv = vlm_batch(KEY, cfg, 2, 32)
    lv, _ = forward(params, cfg, bv)
    cfg_std = cfg.replace(rope="standard")
    ls, _ = forward(params, cfg_std, bv._replace(positions=bv.positions[0]))
    np.testing.assert_allclose(np.asarray(lv), np.asarray(ls), atol=1e-5)


def test_mrope_image_positions_change_output():
    cfg = tiny_dense(rope="mrope", mrope_sections=(2, 3, 3), frontend="vision",
                     arch_type="vlm")
    params = init_model(KEY, cfg)
    b_img = vlm_batch(KEY, cfg, 2, 32, image_patches=12, grid=(3, 4))
    b_txt = b_img._replace(
        positions=jnp.broadcast_to(
            jnp.arange(32, dtype=jnp.int32)[None, None], (3, 2, 32)
        )
    )
    l_img, _ = forward(params, cfg, b_img)
    l_txt, _ = forward(params, cfg, b_txt)
    assert float(jnp.abs(l_img - l_txt).max()) > 1e-4


def test_encoder_masked_prediction():
    cfg = tiny_dense(causal=False, norm="layernorm", act="gelu", rope="none",
                     frontend="audio", arch_type="audio", vocab_size=54,
                     num_kv_heads=4)
    params = init_model(KEY, cfg)
    b = hubert_batch(KEY, cfg, 2, 32)
    logits, _ = forward(params, cfg, b)
    assert logits.shape == (2, 32, 54)
    assert not jnp.isnan(logits).any()
    # bidirectional: future context must influence earlier positions.
    # Perturb row 0's LAST unmasked frame — masked frames are replaced by
    # mask_emb in embed_inputs, so perturbing one of those (e.g. blindly
    # using frame -1) never reaches the model at all.
    col = int(jnp.where(~b.embed_mask[0], jnp.arange(32), -1).max())
    assert col > 0, "fixed-seed batch left row 0 fully masked"
    b2 = b._replace(embeds=b.embeds.at[0, col].add(10.0))
    logits2, _ = forward(params, cfg, b2)
    assert float(jnp.abs(logits2[0, 0] - logits[0, 0]).max()) > 1e-5


def test_moe_dense_topk_selectivity():
    from repro.models.moe import init_moe, moe_dense, route

    cfg = tiny_dense(arch_type="moe", unit=moe_unit(1), num_experts=4,
                     num_experts_per_tok=2, moe_d_ff=32)
    p = init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (64, cfg.d_model))
    w, ids, aux = route(p, x, cfg)
    assert w.shape == (64, 2) and float(aux) > 0
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert int(ids.max()) < 4
    out, _ = moe_dense(p, x.reshape(1, 64, -1), cfg)
    assert not jnp.isnan(out).any()


def test_gqa_head_grouping():
    """GQA output must change when kv heads differ; sanity of reshape."""
    cfg_full = tiny_dense(num_kv_heads=4)
    cfg_gqa = tiny_dense(num_kv_heads=2)
    p_full = init_model(KEY, cfg_full)
    b = lm_batch(KEY, cfg_full, 2, 16)
    out_full, _ = forward(p_full, cfg_full, b)
    assert out_full.shape == (2, 16, 97)
    p_gqa = init_model(KEY, cfg_gqa)
    out_gqa, _ = forward(p_gqa, cfg_gqa, b)
    assert out_gqa.shape == (2, 16, 97)
