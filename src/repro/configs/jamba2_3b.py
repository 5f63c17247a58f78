"""AI21-Jamba2-3B (huggingface.co/ai21labs/AI21-Jamba2-3B, config.json,
model_type ``jamba``) — dense hybrid Mamba-1 + attention.

28L d_model=2560 20H (kv=1, head 128) d_ff=8192 vocab=65536, tied
embeddings, RMSNorm eps 1e-6.  Unit of 14 layers (attn_layer_period 14,
offset 7): attention at layers 7 and 21, Mamba-1 elsewhere (d_inner 5120,
d_state 16, d_conv 4, dt_rank 160, conv bias, no projection bias, RMSNorms on
dt/B/C).  ``num_experts`` is 1, so the "expert" layers are dense SwiGLU MLPs.
No positional encoding: Jamba's attention has no RoPE.
"""
from repro.models.config import ModelConfig, periodic_unit

ARCH_ID = "jamba2-3b"


def get_config(**kw) -> ModelConfig:
    base = dict(
        name=ARCH_ID,
        arch_type="hybrid",
        d_model=2560,
        vocab_size=65536,
        unit=periodic_unit(14, 7, 2, 1, moe=False),
        num_units=2,
        num_heads=20,
        num_kv_heads=1,
        d_ff=8192,
        rope="none",
        norm_eps=1e-6,
        tie_embeddings=True,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=160,
        citation="huggingface.co/ai21labs/AI21-Jamba2-3B",
    )
    base.update(kw)
    return ModelConfig(**base)


def smoke_config() -> ModelConfig:
    """Two units of four layers (Mamba, Mamba, attention, Mamba)."""
    return get_config(unit=periodic_unit(4, 2, 2, 1, moe=False), num_units=2,
                      d_model=64, num_heads=4, num_kv_heads=1, d_ff=128,
                      vocab_size=512, mamba_dt_rank=8)
