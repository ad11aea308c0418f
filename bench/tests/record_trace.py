#!/usr/bin/env python3
"""Record the small profiler trace that bench/tests/test_tracereduce.py
reduces. Run on a TPU:

    python bench/tests/record_trace.py <out_dir>

Five steps, each a ``bench.engine_step`` host span around one jitted
program (a matrix product and one ``paged_decode_attention`` kernel
call), then a ``bench.bookkeeping`` span in which the host sleeps 2 ms
and the chip has nothing to do, all inside one ``bench.window`` span.
Writes the ``.xplane.pb`` under ``<out_dir>`` and ``expected.json``
beside it: what the host knows of the run (steps, sleeps).
"""
from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.paged_attention.paged_attention import (  # noqa: E402
    paged_decode_attention_kernel,
)

STEPS, SLEEP_S = 5, 0.002
B, H, KV, HD, BS, MB = 8, 16, 2, 128, 16, 64
NB = B * MB + 1


def main(out: Path) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record this trace on a TPU")
    kern = functools.partial(paged_decode_attention_kernel, n_kv=KV, window=0,
                             scale=HD ** -0.5, interpret=False)

    @jax.jit
    def fixture_step(x, q, kn, vn, kp, vp, table, pos):
        y = jnp.tanh(x @ x)
        att = kern(q, kn, vn, kp, vp, table, pos)
        return y, att

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    args = (jax.random.normal(ks[0], (2048, 2048), jnp.bfloat16),
            jax.random.normal(ks[1], (B, 1, H, HD), jnp.bfloat16),
            jax.random.normal(ks[2], (B, KV * HD), jnp.bfloat16),
            jax.random.normal(ks[3], (B, KV * HD), jnp.bfloat16),
            jax.random.normal(ks[4], (NB, BS, KV * HD), jnp.bfloat16),
            jax.random.normal(ks[5], (NB, BS, KV * HD), jnp.bfloat16),
            jnp.arange(B * MB, dtype=jnp.int32).reshape(B, MB) + 1,
            jnp.full((B,), MB * BS - 1, jnp.int32))
    jax.block_until_ready(fixture_step(*args))
    out.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                jax.block_until_ready(fixture_step(*args))
            with jax.profiler.TraceAnnotation("bench.bookkeeping"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    with open(out / "expected.json", "w") as f:
        json.dump({"steps": STEPS, "sleep_s": SLEEP_S, "device_kind": jax.devices()[0].device_kind,
                   "kernel_calls": STEPS, "program": "fixture_step"}, f)


if __name__ == "__main__":
    main(Path(sys.argv[1]))
