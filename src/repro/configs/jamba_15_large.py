"""Jamba-1.5-Large 398B [arXiv:2403.19887] — hybrid Mamba+attention 1:7, MoE.

72L d_model=8192 64H (kv=8) d_ff=24576 vocab=65536, MoE 16 experts top-2.
Unit of 8 layers (attn_layer_period 8, offset 4): attention at layer 4, Mamba
elsewhere; MoE MLP on the odd layers (expert_layer_period 2, offset 1).  No
positional encoding: Jamba's attention has no RoPE.  Hybrid: long_500k runs
(bounded attention fraction).
"""
from repro.models.config import ModelConfig, periodic_unit

ARCH_ID = "jamba-1.5-large-398b"


def get_config(**kw) -> ModelConfig:
    base = dict(
        name=ARCH_ID,
        arch_type="hybrid",
        d_model=8192,
        vocab_size=65536,
        unit=periodic_unit(8, 4, 2, 1, moe=True),
        num_units=9,
        num_heads=64,
        num_kv_heads=8,
        d_ff=24576,
        moe_d_ff=24576,
        num_experts=16,
        num_experts_per_tok=2,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        rope="none",
        citation="arXiv:2403.19887",
    )
    base.update(kw)
    return ModelConfig(**base)


def smoke_config() -> ModelConfig:
    return get_config(unit=periodic_unit(2, 0, 2, 1, moe=True), num_units=1,
                      d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                      moe_d_ff=256, vocab_size=1024, num_experts=4,
                      num_experts_per_tok=2, mamba_d_state=8)
