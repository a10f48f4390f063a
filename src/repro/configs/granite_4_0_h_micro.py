"""granite-4.0-h-micro — hybrid Mamba-2 + GQA attention, 9 Mamba-2 : 1
attention layer per period of 10, NoPE, Granite's embedding, residual,
attention and logit multipliers.
[hf:ibm-granite/granite-4.0-h-micro, model_type granitemoehybrid]"""
from repro.configs.base import BLOCK_ATTN, BLOCK_MAMBA2, ModelConfig

M, A = BLOCK_MAMBA2, BLOCK_ATTN

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,                # shared_intermediate_size (every layer)
    vocab_size=100_352,
    tie_embeddings=True,
    rope_theta=0.0,           # position_embedding_type "nope"
    norm_eps=1e-5,
    block_pattern=(M, M, M, M, M, A, M, M, M, M),
    ssm_heads=64,             # mamba_n_heads of mamba_d_head 64: 4096 wide
    ssm_head_dim=64,
    ssm_state=128,
    ssm_chunk=256,
    conv1d_width=4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
)


def reduced() -> ModelConfig:
    return CONFIG.replace(name="granite-4.0-h-micro-reduced", n_layers=4,
                          d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                          d_ff=128, vocab_size=256, block_pattern=(M, A),
                          ssm_heads=8, ssm_head_dim=16, ssm_state=16,
                          ssm_chunk=8)
