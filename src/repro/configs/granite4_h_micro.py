"""granite-4.0-h-micro — Mamba2 / attention hybrid with µP multipliers.

[hf:ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid]
40L: 36 Mamba2 (64 SSD heads × 64, d_inner 4096, state 128, one group,
conv 4, chunk 256) and 4 GQA attention layers at positions 5, 15, 25, 35
(32H, kv 8, head_dim 64, no positional embedding, softmax scale 1/64) ·
a SwiGLU MLP (8192) in every layer · d_model 2048 · vocab 100352, tied ·
embedding × 12, residual branches × 0.22, logits ÷ 8. No experts
(num_local_experts 0), so the MLP is the config's shared MLP.
"""
from repro.config.base import ModelConfig, SSMConfig
from repro.config.registry import register_arch

# the published layer_types: attention at 5, 15, 25, 35
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))


def full() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro",
        family="interleaved",
        n_layers=40,
        layer_types=LAYER_TYPES,
        d_model=2048,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab_size=100352,
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, chunk_size=256,
                      conv_width=4),
        position_embedding="nope",
        attention_multiplier=0.015625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        subquadratic=True,
        ce_chunk=512,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="granite-smoke",
        family="interleaved",
        n_layers=3,
        layer_types=("mamba", "attention", "mamba"),
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab_size=512,
        tie_embeddings=True,
        ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, chunk_size=8),
        position_embedding="nope",
        attention_multiplier=0.0625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        subquadratic=True,
    )


register_arch("granite-4.0-h-micro", full, smoke)
