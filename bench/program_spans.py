#!/usr/bin/env python3
"""The program's own host spans in a run's profiler trace, and the
per-layer numbers read from them.

The serving engine opens ``repro.obs.trace`` spans (``engine.*``,
``kv.*``, ``sched.*``) that reach a running ``jax.profiler`` session as
TraceAnnotations, attributes included, on the profiler's host clock.
A span's parent is the span that encloses it on the same host thread.
A span whose name ends in ``_wait`` is the host waiting on the chip;
every other span is host work.

``for_run(rec)`` finds the run's ``.xplane.pb`` under ``.bench_trace/``
(the one whose ``bench.window`` span is the record's window) and loads
it once. A program without these spans gives an empty list, and the
readers then give None.

    python bench/program_spans.py <trace.xplane.pb> [--max-batch N]

prints device 0's idle time in the window split by program span,
innermost first, and the four per-step numbers, as one JSON object.
The device's clock is first brought to agree with the program's spans
(``clock_correction``): ``tracereduce.align`` takes the middle of the
offsets that put the most programs inside a step, which can leave it
ten milliseconds out when steps follow each other closely.
"""
from __future__ import annotations

import bisect
import functools
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT)]

from bench import trace_names as tn  # noqa: E402
from bench import tracereduce as tr  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
PREFIXES = ("engine.", "kv.", "sched.")
STEP = "engine.step"
DECODE = "engine.decode"  # one per decode step: "per step" divides by these
ADMIT = "engine.admit"
SAMPLE_PROGRAM = "jit_sample_last"
MATCH_NS = 40e6  # well under a decode step of the chip cell (~166 ms)


class Span:
    __slots__ = ("name", "start", "end", "args", "children")

    def __init__(self, name: str, start: float, end: float, args: dict):
        self.name, self.start, self.end, self.args = name, start, end, args
        self.children: list[Span] = []


def is_wait(name: str) -> bool:
    return name.endswith("_wait")


def _nest(events: list[Span]) -> None:
    """Give each span of one thread its children: the spans it encloses
    directly."""
    stack: list[Span] = []
    for sp in sorted(events, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            stack.pop()
        if stack and sp.end <= stack[-1].end:
            stack[-1].children.append(sp)
        stack.append(sp)


@functools.lru_cache(maxsize=4)
def load(xplane: str) -> dict:
    """``window``: the (start, end) of the ``bench.window`` span, or
    None; ``spans``: every program span on the host, nested per
    thread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread = []
            for e in line.events:
                name = e.name
                if name.startswith(PREFIXES):
                    thread.append(Span(name, e.start_ns, e.end_ns, dict(e.stats)))
                elif name == tr.WINDOW_SPAN:
                    window = (e.start_ns, e.end_ns)
            _nest(thread)
            spans += thread
    return {"window": window, "spans": spans}


def for_run(rec: dict, trace_dir: Path = TRACE_DIR) -> list[Span] | None:
    """The program spans of the run whose window is ``rec``'s."""
    want = (rec["lo"], rec["hi"])
    paths = sorted(Path(trace_dir).glob("**/*.xplane.pb"), key=lambda p: -p.stat().st_mtime)
    for path in paths:
        got = load(str(path))
        if got["window"] == want:
            return got["spans"]
    return None


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def _covered(intervals, lo: float, hi: float) -> float:
    return tr.covered(tr.merge(intervals), lo, hi)


def _topmost(sp: Span, pred) -> list[Span]:
    """The descendants of ``sp`` that satisfy ``pred`` and have no
    ancestor below ``sp`` that does."""
    out = []
    for c in sp.children:
        out += [c] if pred(c.name) else _topmost(c, pred)
    return out


def in_window(spans, name: str, lo: float, hi: float) -> list[Span]:
    return [s for s in spans if s.name == name and s.end > lo and s.start < hi]


def steps(spans, lo: float, hi: float) -> int:
    return len(in_window(spans, DECODE, lo, hi))


def less_ns(spans, name: str, minus, lo: float, hi: float) -> float:
    """In-window time of the spans called ``name``, less the time of
    their descendants that satisfy ``minus``."""
    total = 0.0
    for sp in in_window(spans, name, lo, hi):
        s, e = max(sp.start, lo), min(sp.end, hi)
        total += (e - s) - _covered([(c.start, c.end) for c in _topmost(sp, minus)], s, e)
    return total


def self_ns(spans, pred, lo: float, hi: float) -> float:
    """In-window self time (less every child span) of the spans whose
    name satisfies ``pred``."""
    total = 0.0
    for sp in spans:
        if pred(sp.name) and sp.end > lo and sp.start < hi:
            s, e = max(sp.start, lo), min(sp.end, hi)
            total += (e - s) - _covered([(c.start, c.end) for c in sp.children], s, e)
    return total


def engine_host_ns(spans, lo: float, hi: float) -> float:
    """``engine.step`` less its ``*_wait`` descendants."""
    return less_ns(spans, STEP, is_wait, lo, hi)


def admit_ns(spans, lo: float, hi: float) -> float:
    """``engine.admit`` less its KV store, prefill and wait descendants:
    the scheduler's take, prefix lookups, slot bookkeeping."""
    def minus(n: str) -> bool:
        return n.startswith("kv.") or n == "engine.prefill" or is_wait(n)

    return less_ns(spans, ADMIT, minus, lo, hi)


def kv_host_ns(spans, lo: float, hi: float) -> float:
    """Self time of the KV store's spans other than its waits."""
    return self_ns(spans, lambda n: n.startswith("kv.") and not is_wait(n), lo, hi)


def occupancy(spans, lo: float, hi: float, max_batch: int) -> float | None:
    """Mean ``rows`` of the decode steps over ``max_batch``, in %."""
    rows = [int(s.args["rows"]) for s in in_window(spans, DECODE, lo, hi)]
    return 100.0 * sum(rows) / len(rows) / max_batch if rows else None


def per_step_ms(rec: dict, reader) -> float | None:
    """``reader(spans, lo, hi)`` (ns) per decode step of the run, in ms."""
    spans = for_run(rec)
    n = steps(spans, rec["lo"], rec["hi"]) if spans else 0
    return reader(spans, rec["lo"], rec["hi"]) / n / 1e6 if n else None


def clock_correction(dev: dict, spans) -> float:
    """How far (ns) to move the device's events, already aligned by
    ``tracereduce.align``, so that they agree with the program's spans:
    each decode program starts after its step's ``kv.kernel_view`` ends
    (it is dispatched after it), and the sample program after it ends
    before the step's ``engine.token_wait`` does (the wait is for the
    sampled tokens). The middle of the range those bounds leave, or 0
    where they leave none. A decode program is matched to the step
    whose ``kv.kernel_view`` ends within ``MATCH_NS`` of its start."""
    decodes = sorted((m for m in dev["modules"] if tn.matches(tn.DECODE)(m[0])),
                     key=lambda m: m[1])
    samples = sorted((m for m in dev["modules"] if SAMPLE_PROGRAM in m[0]), key=lambda m: m[1])
    sample_starts = [m[1] for m in samples]
    lower, upper = [], []
    for step in spans:
        if step.name != STEP:
            continue
        kids = {c.name: c for c in step.children}
        dec, wait = kids.get(DECODE), kids.get("engine.token_wait")
        view = dec and next((c for c in dec.children if c.name == "kv.kernel_view"), None)
        if not (view and wait):
            continue
        near = [m for m in decodes if abs(m[1] - view.end) < MATCH_NS]
        if len(near) != 1:
            continue
        lower.append(view.end - near[0][1])
        k = bisect.bisect_left(sample_starts, near[0][2])
        if k < len(samples):
            upper.append(wait.end - samples[k][2])
    if not lower or not upper or max(lower) > min(upper):
        return 0.0
    return (max(lower) + min(upper)) / 2


def idle_by_span(dev: dict, spans, host: list, lo: float, hi: float,
                 shift: float = 0.0) -> dict:
    """The device's idle time in [lo, hi] split by the innermost host
    span (program spans and the benchmark's own ``host`` spans, the
    window's excepted) open at each moment, in seconds; idle time with
    no span open under ``no span``. Spans of different threads that
    overlap go to the shorter. ``shift`` (ns) moves the device's ops
    first (``clock_correction``)."""
    idle = tr.gaps(tr.merge((s + shift, e + shift) for _, s, e in dev["ops"]), lo, hi)
    every = [(s.name, s.start, s.end) for s in spans]
    every += [(n, s, e) for n, s, e in host if n != tr.WINDOW_SPAN]
    every = [(n, max(s, lo), min(e, hi), e - s) for n, s, e in every if e > lo and s < hi]
    marks = sorted([(s, 1, i) for i, (_, s, _, _) in enumerate(every)]
                   + [(e, 0, i) for i, (_, _, e, _) in enumerate(every)])
    starts = [a for a, _ in idle]
    open_: set[int] = set()
    by: dict[str, float] = {}
    t = lo
    for x, kind, i in marks + [(hi, 0, -1)]:
        if x > t:
            # idle time in [t, x] goes to the innermost open span
            k = bisect.bisect_right(starts, t) - 1
            got = 0.0
            for a, b in idle[max(k, 0):]:
                if a >= x:
                    break
                got += _clip(a, b, t, x)
            if got:
                key = (every[min(open_, key=lambda j: every[j][3])][0]
                       if open_ else "no span")
                by[key] = by.get(key, 0.0) + got / 1e9
            t = x
        if i >= 0:
            (open_.add if kind else open_.discard)(i)
    return dict(sorted(by.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--max-batch", type=int, default=32)
    args = ap.parse_args(argv)
    trace = tr.load(args.xplane)
    lo, hi = tr.window(trace)
    spans = load(args.xplane)["spans"]
    dev = trace["devices"][sorted(trace["devices"])[0]]
    shift = clock_correction(dev, spans)
    split = idle_by_span(dev, spans, trace["host"], lo, hi, shift)
    n = steps(spans, lo, hi)
    idle_s = sum(split.values())
    out = {"window_s": (hi - lo) / 1e9, "idle_s": idle_s, "steps": n,
           "align_offset_ms": trace["offset_ns"] / 1e6, "clock_correction_ms": shift / 1e6,
           "idle_by_span_s": split,
           "idle_share_by_span": {k: v / idle_s for k, v in split.items()} if idle_s else {}}
    if n:
        out.update({
            "engine_host_ms": engine_host_ns(spans, lo, hi) / n / 1e6,
            "admit_ms": admit_ns(spans, lo, hi) / n / 1e6,
            "kv_host_ms": kv_host_ns(spans, lo, hi) / n / 1e6,
            "batch_occupancy": occupancy(spans, lo, hi, args.max_batch),
        })
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
