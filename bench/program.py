"""How the benchmark builds the system under test from a configuration
file: the published Qwen2-architecture keys mapped onto the program's
own ``ArchConfig``, and which leaves are stored in which dtype."""
from __future__ import annotations

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def arch_config(cfg: dict, compute_dtype: str):
    """The program's ArchConfig for a Qwen2-architecture config file."""
    from repro.configs.base import ArchConfig

    if cfg["model_type"] != "qwen2" or cfg["hidden_act"] != "silu":
        raise ValueError(f"not a Qwen2 configuration: {cfg['model_type']!r}")
    if cfg.get("use_sliding_window"):
        raise ValueError("sliding-window Qwen2 layers are not supported")
    return ArchConfig(
        name=cfg["name"],
        family="dense",
        source=cfg["source"],
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        qkv_bias=True,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=DTYPES[compute_dtype],
    )


def stored_dtype(weights_dtype: str):
    """Matrices, biases and embeddings in ``weights_dtype``; norm scales
    in float32, as the program reads them."""
    wd = DTYPES[weights_dtype]

    def of(name: str):
        return wd if name.rsplit("/", 1)[-1] in ("w", "b", "table") else jnp.float32

    return of
