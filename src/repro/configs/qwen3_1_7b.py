"""Qwen3-1.7B: dense decoder LM with qk-norm and GQA.

Source: https://huggingface.co/Qwen/Qwen3-1.7B/blob/main/config.json.
28L d_model=2048 16H (GQA kv=8) d_ff=6144 vocab=151936 (tied), head_dim=128,
RMSNorm on q/k per head (eps 1e-6), RoPE theta 1e6, bf16.
"""
from repro.config import ModelConfig, replace

CONFIG = ModelConfig(
    arch_id="qwen3-1.7b",
    family="dense",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=8,
    head_dim=128,
    d_ff=6144,
    vocab_size=151936,
    qk_norm=True,
    mlp_act="silu",
    gated_mlp=True,
    rope_theta=1e6,
    tie_embeddings=True,
    source="https://huggingface.co/Qwen/Qwen3-1.7B/blob/main/config.json",
)


def smoke_config() -> ModelConfig:
    return replace(
        CONFIG,
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256,
    )
