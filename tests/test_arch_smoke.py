"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step on CPU, shape + finiteness assertions; decode step for
decoder archs (deliverable f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.launch.mesh import make_host_mesh
from repro.launch.shardings import ShardingPolicy
from repro.launch.steps import TrainState, make_train_step
from repro.models import decode_step, forward, init_model, prefill
from repro.models.frontends import hubert_batch, lm_batch, vlm_batch
from repro.optim import adamw

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def make_batch(cfg):
    if cfg.frontend == "audio":
        return hubert_batch(KEY, cfg, B, S)
    if cfg.frontend == "vision":
        return vlm_batch(KEY, cfg, B, S, image_patches=6, grid=(2, 3))
    return lm_batch(KEY, cfg, B, S)


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_smoke_forward(arch):
    cfg = registry.smoke_config(arch)
    assert cfg.d_model <= 512 and cfg.num_experts <= 4
    params = init_model(KEY, cfg)
    batch = make_batch(cfg)
    logits, aux = forward(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits, np.float32)).any()


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = registry.smoke_config(arch)
    mesh = make_host_mesh(1, 1)
    pol = ShardingPolicy(dp_axes=("data",), dp_sizes=(1,), model_axis_size=1, fsdp=False)
    opt = adamw(1e-3)
    step = make_train_step(cfg, opt, mesh, pol, mode="standard")
    params = init_model(KEY, cfg)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    batch = make_batch(cfg)
    new_state, metrics = jax.jit(step)(state, batch, None)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        new_state.params, params,
    )
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize(
    "arch",
    [a for a in registry.ARCH_IDS
     if registry.smoke_config(a).is_decoder()],
)
def test_smoke_decode_step(arch):
    cfg = registry.smoke_config(arch)
    params = init_model(KEY, cfg)
    batch = make_batch(cfg)
    _, cache = prefill(params, cfg, batch, max_len=S + 4)
    tok = jnp.full((B, 1), 1, jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    mrope = (jnp.broadcast_to(pos[None, :, None], (3, B, 1))
             if cfg.rope == "mrope" else None)
    logits, new_cache = decode_step(
        params, cfg, tok, pos, cache, mrope_position=mrope
    )
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits, np.float32)).any()


def test_encoder_has_no_decode():
    cfg = registry.smoke_config("hubert-xlarge")
    with pytest.raises(ValueError):
        decode_step(init_model(KEY, cfg), cfg, jnp.zeros((1, 1), jnp.int32),
                    jnp.zeros((1,), jnp.int32), {})


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_full_config_matches_spec(arch):
    """The FULL configs carry the exact assigned hyper-parameters."""
    cfg = registry.get_config(arch)
    spec = {
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "rwkv6-7b": (32, 4096, 0, 0, 14336, 65536),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "jamba2-3b": (28, 2560, 20, 1, 8192, 65536),
        "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
    }[arch]
    L, D, H, KV, FF, V = spec
    assert cfg.num_layers == L and cfg.d_model == D
    assert cfg.num_heads == H and cfg.num_kv_heads == KV
    assert cfg.d_ff == FF and cfg.vocab_size == V
    moe = {
        "mixtral-8x7b": (8, 2),
        "qwen3-moe-30b-a3b": (128, 8),
        "jamba-1.5-large-398b": (16, 2),
    }
    if arch in moe:
        assert (cfg.num_experts, cfg.num_experts_per_tok) == moe[arch]
    else:
        assert cfg.num_experts == 0
