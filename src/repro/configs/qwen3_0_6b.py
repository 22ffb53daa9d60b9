"""Qwen3-0.6B — dense, GQA, qk_norm, head_dim 128.  [hf:Qwen/Qwen3-0.6B]"""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-0.6b", family="dense",
        n_layers=28, d_model=1024, n_heads=16, n_kv_heads=8, head_dim=128,
        d_ff=3072, vocab_size=151_936,
        qk_norm=True, rope_theta=1_000_000.0,
        tie_embeddings=True,
        source="[hf:Qwen/Qwen3-0.6B]",
        max_seq_len=32_768)
