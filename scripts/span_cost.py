#!/usr/bin/env python3
"""What one ``repro.obs.trace`` span costs the host, in microseconds.

    python scripts/span_cost.py [--n 200000]

Times ``with span(name, track, rows=...): pass`` in a tight loop, less
the same loop without the span, in four states: nothing on (the null
span), a TraceGraph tracer installed, a ``jax.profiler`` session with
its default options (Python function tracing on), and a session with
Python function tracing off. Prints one JSON object; run it on the
host whose cost is wanted.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src")]


def _per_span_us(n: int, with_span: bool) -> float:
    from repro.obs import trace

    track = ("engine", "decode")
    t0 = time.perf_counter()
    if with_span:
        for i in range(n):
            with trace.span("engine.decode", track, rows=i):
                pass
    else:
        for i in range(n):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def cost_us(n: int) -> float:
    """Best of five: the span loop less the empty loop, per span."""
    return min(_per_span_us(n, True) for _ in range(5)) - min(
        _per_span_us(n, False) for _ in range(5))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    import jax

    from repro.obs import trace

    out = {"platform": jax.devices()[0].platform, "n": args.n}
    out["off_us"] = cost_us(args.n)
    trace.enable(capacity=1 << 16)
    out["tracer_us"] = cost_us(args.n)
    trace.disable()
    for key, level in (("profiler_us", None), ("profiler_no_python_tracer_us", 0)):
        opts = None
        if level is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = level
        with tempfile.TemporaryDirectory(prefix="span_cost") as d:
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                out[key] = cost_us(args.n // 10)
            finally:
                jax.profiler.stop_trace()
    out["off_is_null_span"] = trace.span("x") is trace.span("y")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
