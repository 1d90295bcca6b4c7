"""Granite-4.0-H-Micro [hf:ibm-granite/granite-4.0-h-micro, config.json;
model_type granitemoehybrid]: 40 layers set by ``layer_types`` -- Mamba-2
mixers everywhere but GQA attention at layers 5, 15, 25 and 35 -- each
followed by a SwiGLU MLP (``shared_intermediate_size`` 8192, no experts).
NoPE attention (no RoPE), muP multipliers, tied embeddings.
"""
from .base import ModelConfig, register

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = register(ModelConfig(
    name="granite_4_0_h_micro", family="hybrid",
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8,
    d_ff=8192, vocab_size=100352, head_dim=64,
    position_embedding="nope", norm_eps=1e-5, tie_embeddings=True,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0,
    layer_types=_PERIOD * 4,
    ssm_state=128, ssm_heads=64, ssm_expand=2,
    notes="Mamba-2 + NoPE GQA attention from a per-layer pattern; the "
          "model stack's two-kind cache (K/V and SSM state).",
))
