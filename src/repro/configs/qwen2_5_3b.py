"""qwen2.5-3b — dense LM, GQA + QKV bias [hf:Qwen/Qwen2.5-3B; hf].

36L d_model=2048 16H (GQA kv=2) d_ff=11008 vocab=151936.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="qwen2.5-3b",
    family="dense",
    source="hf:Qwen/Qwen2.5-3B; hf",
    n_layers=36,
    d_model=2048,
    n_heads=16,
    n_kv_heads=2,
    d_ff=11008,
    vocab_size=151936,
    qkv_bias=True,
    rope_theta=1_000_000.0,
    tie_embeddings=True,
    supports_long_context=False,
)

SMOKE = ArchConfig(
    name="qwen2.5-smoke",
    family="dense",
    n_layers=2,
    d_model=128,
    n_heads=8,
    n_kv_heads=2,
    d_ff=256,
    vocab_size=512,
    qkv_bias=True,
    tie_embeddings=True,
)
