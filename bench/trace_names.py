"""How the program's compiled programs and kernels show in a profiler
trace. A program (an "XLA Module" event) carries the name of the Python
function its jit wraps (``jit_decode_step_paged(<fingerprint>)``);
packed prefill is an anonymous ``lambda`` of ``PrefillRunner``. An op
carries its HLO text, and a Pallas kernel is a custom call to
``tpu_custom_call``, known by the program it runs in."""
from __future__ import annotations

from bench.tracereduce import KERNEL_TARGET

DECODE = ("jit_decode_step_paged",)
PREFILL = ("jit__lambda",)
KV_STORE = ("scatter_rows", "migrate_cache_into_blocks", "absorb_rows", "zero_block_tail",
            "paged_gather")


def matches(keys: tuple[str, ...]):
    def pred(name: str) -> bool:
        return any(k in name for k in keys)

    return pred


def is_kernel(name: str) -> bool:
    return KERNEL_TARGET in name


def device0(rec: dict) -> dict:
    return rec["trace"]["devices"][sorted(rec["trace"]["devices"])[0]]


def devices(rec: dict) -> list[dict]:
    return [rec["trace"]["devices"][d] for d in sorted(rec["trace"]["devices"])[: rec["chips"]]]
