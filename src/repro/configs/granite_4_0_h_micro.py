"""granite-4.0-h-micro — Mamba2/attention hybrid, each layer its own mixer
and its own SwiGLU MLP [hf:ibm-granite/granite-4.0-h-micro config.json].

40 layers at d_model 2048: layers 5, 15, 25 and 35 are NoPE grouped-query
attention (32 heads, 8 KV heads, head_dim 64), the other 36 Mamba2 mixers
(64 heads x 64, d_state 128, one group, conv kernel 4 with bias, chunk
256). Embedding x12, each branch x0.22 before its residual add, attention
scores x1/64, logits /8, RMSNorm eps 1e-5. The published model ties the
head to the embedding; the program has no tied path, so the head is a
matrix of its own.
"""
from repro.configs.base import ModelConfig, SSMConfig

_ATTENTION = (5, 15, 25, 35)

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    rope_theta=10000.0,
    position_embedding="nope",
    attention_multiplier=0.015625,
    ssm=SSMConfig(kind="mamba2", state_size=128, num_heads=64, expand=2,
                  chunk_size=256),
    layer_types=tuple("attention" if i in _ATTENTION else "mamba"
                      for i in range(40)),
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    source="hf:ibm-granite/granite-4.0-h-micro config.json "
           "(granitemoehybrid); Mamba2 + NoPE GQA at layers 5/15/25/35",
)
