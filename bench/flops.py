"""Operations a Qwen2-architecture model requires, from its published
sizes: what the FLOP utilizations (``mfu.*``) divide by the chip's peak.
Recomputed operations (rematerialization, padding) do not count."""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    dq = d  # num_attention_heads * head_dim, head_dim = hidden / heads
    dkv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    ff = cfg["intermediate_size"]
    return d, dq, dkv, ff, cfg["num_hidden_layers"], cfg["vocab_size"]


def layer_matmul_params(cfg: dict) -> int:
    d, dq, dkv, ff, L, _ = _dims(cfg)
    return L * (d * dq + 2 * d * dkv + dq * d + 3 * d * ff)


def decode_token(cfg: dict, attended: int) -> float:
    """One generated token whose attention reads ``attended`` positions:
    the layers' matrices, attention over the context, the logits."""
    d, dq, _, _, L, V = _dims(cfg)
    return 2.0 * layer_matmul_params(cfg) + 4.0 * dq * attended * L + 2.0 * V * d


def prefill(cfg: dict, n: int) -> float:
    """A prompt of ``n`` tokens: causal attention, logits of its last
    position only."""
    d, dq, _, _, L, V = _dims(cfg)
    attn = 4.0 * dq * L * n * (n + 1) / 2
    return 2.0 * layer_matmul_params(cfg) * n + attn + 2.0 * V * d


def train_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a ``seq``-token row: three
    times the forward's matrices (logits of every position included) and
    causal attention."""
    d, dq, _, _, L, V = _dims(cfg)
    fwd = 2.0 * (layer_matmul_params(cfg) + V * d) + 4.0 * dq * L * (seq + 1) / 2
    return 3.0 * fwd
