"""The Qwen2 architecture (Qwen1.5 and Qwen2.5 share it): decoder-only,
RMSNorm, rotary positions, grouped-query attention with biases on q/k/v
in every layer, SwiGLU MLP, tied or untied unembedding. Its reference is
``bench/reference/qwen2.py``."""
from __future__ import annotations

import jax.numpy as jnp

from bench.arch import DTYPES

# the rehearsal's widths (bench/rehearse.py)
TINY = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "vocab_size": 512, "num_hidden_layers": 2}


def arch_config(cfg: dict, compute_dtype: str):
    """The program's ArchConfig for a Qwen2-architecture config file."""
    from repro.configs.base import ArchConfig

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"Qwen2 with hidden_act {cfg['hidden_act']!r} is not supported")
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
        head_dim=cfg.get("head_dim", 0),  # 0: hidden / heads
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


def attention(cfg: dict) -> dict:
    """Every layer attends; ``head_dim`` is the file's, or hidden / heads."""
    h = cfg["num_attention_heads"]
    return {"layers": list(range(cfg["num_hidden_layers"])), "heads": h,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or cfg["hidden_size"] // h}


def _dims(cfg: dict):
    att = attention(cfg)
    dq = att["heads"] * att["head_dim"]
    dkv = att["kv_heads"] * att["head_dim"]
    return (cfg["hidden_size"], dq, dkv, cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def layer_matmul_params(cfg: dict) -> int:
    d, dq, dkv, ff, L, _ = _dims(cfg)
    return L * (d * dq + 2 * d * dkv + dq * d + 3 * d * ff)


def decode_token(cfg: dict, attended: int) -> float:
    """The layers' matrices, attention over the context, the logits."""
    d, dq, _, _, L, V = _dims(cfg)
    return 2.0 * layer_matmul_params(cfg) + 4.0 * dq * attended * L + 2.0 * V * d


def prefill(cfg: dict, n: int) -> float:
    """Causal attention; logits of the last position only."""
    d, dq, _, _, L, V = _dims(cfg)
    attn = 4.0 * dq * L * n * (n + 1) / 2
    return 2.0 * layer_matmul_params(cfg) * n + attn + 2.0 * V * d


def train_token(cfg: dict, seq: int) -> float:
    """Three times the forward's matrices (logits of every position
    included) and causal attention."""
    d, dq, _, _, L, V = _dims(cfg)
    fwd = 2.0 * (layer_matmul_params(cfg) + V * d) + 4.0 * dq * L * (seq + 1) / 2
    return 3.0 * fwd


def tiny(cfg: dict) -> dict:
    """Every width cut to ``TINY``; an MHA model stays MHA, a GQA model
    keeps at most 2 KV heads."""
    out = dict(cfg, **TINY)
    mha = cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    out["num_key_value_heads"] = (TINY["num_attention_heads"] if mha
                                  else min(cfg["num_key_value_heads"], 2))
    return out
