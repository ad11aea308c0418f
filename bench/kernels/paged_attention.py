"""What one decode step's ``paged_attention`` calls (one per attending
layer, as the architecture's ``attention(cfg)`` lists them) require:
operations and HBM bytes, from the attended length of every active
slot. Bytes are the keys and values the step attends (stored in
``kv_bytes`` per element) plus the queries read and outputs written in
bf16; blocks the kernel reads past a slot's length are not required and
are not counted."""
from __future__ import annotations

from bench import arch


def cost(cfg: dict, attended: list[int], kv_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over all attending layers."""
    att = arch.load(cfg).module.attention(cfg)
    hd = att["head_dim"]
    dq = att["heads"] * hd
    dkv = att["kv_heads"] * hd
    L = len(att["layers"])
    ctx = float(sum(attended))
    flops = 4.0 * dq * ctx * L  # q.k over the context, then p.v
    nbytes = (2.0 * dkv * kv_bytes * ctx + 2.0 * 2 * dq * len(attended)) * L
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
