"""Operations a model requires, from its published sizes: what the FLOP
utilizations (``mfu.*``) divide by the chip's peak. Recomputed
operations (rematerialization, padding) do not count. Each count is the
architecture's own (``bench/arch/<model_type>.py``)."""
from __future__ import annotations

from bench import arch


def decode_token(cfg: dict, attended: int) -> float:
    """One generated token whose attention reads ``attended`` positions."""
    return arch.load(cfg).module.decode_token(cfg, attended)


def prefill(cfg: dict, n: int) -> float:
    """A prompt of ``n`` tokens, up to the logits of its last position."""
    return arch.load(cfg).module.prefill(cfg, n)


def train_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a ``seq``-token row."""
    return arch.load(cfg).module.train_token(cfg, seq)
