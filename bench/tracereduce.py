"""From a profiler trace to numbers: the events of the benchmark's host
spans and of each device, and the interval arithmetic the per-layer
metrics share (busy union, time by name, idle gaps and what the host
was doing in them, collective time not covered by compute).

A trace is reduced to plain lists first (``load``), so the arithmetic
is tested on a recorded trace without a chip. Times are nanoseconds on
the profiler's clock, on which host and device events are aligned.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

HOST_PREFIX = "bench."  # the benchmark's own TraceAnnotations
WINDOW_SPAN = "bench.window"
STEP_SPANS = ("bench.engine_step", "bench.train_step")  # all device work of a step ends inside
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|send|recv)(-start|-done)?\(")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'  # a Pallas (Mosaic) kernel


def load(xplane: str | Path) -> dict:
    """Host spans named ``bench.*`` and, per TPU, its ops, its async ops
    (transfers in flight) and its programs (modules), as
    ``[name, start_ns, end_ns]``. An op's name is its HLO text. Device
    times are moved onto the host's clock (``align``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    host, devices, cpu_ops = [], {}, []
    for plane in pd.planes:
        name = plane.name
        if re.fullmatch(r"/device:TPU:\d+", name):
            dev = {"ops": [], "async": [], "modules": []}
            key = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}
            for line in plane.lines:
                if line.name in key:
                    dev[key[line.name]] = [[e.name, e.start_ns, e.end_ns] for e in line.events]
            devices[name.rsplit("/", 1)[-1]] = dev
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.end_ns])
                    elif name == "/host:CPU" and any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append([e.name, e.start_ns, e.end_ns])
    if not devices and cpu_ops:  # a CPU rehearsal: its XLA ops stand in
        devices["CPU:0"] = {"ops": cpu_ops, "async": [], "modules": []}
    trace = {"host": host, "devices": devices}
    align(trace)
    return trace


def align(trace: dict, limit_ns: float = 20e6) -> float:
    """Shift every device's events by the one offset that puts the most
    device programs inside a step span of the host (a step waits for its
    device work, so each program runs inside one), taking the middle of
    the best range. The device's clock in a TPU trace runs a millisecond
    or two behind the host's. Returns the offset (ns)."""
    steps = sorted((s, e) for n, s, e in trace["host"] if n in STEP_SPANS)
    mods = [(s, e) for d in trace["devices"].values() for _, s, e in d["modules"]]
    if not steps or not mods:
        trace["offset_ns"] = 0.0
        return 0.0
    starts = [s for s, _ in steps]
    edges = []
    for ms, me in mods:
        lo_i = bisect.bisect_left(starts, ms - limit_ns)
        hi_i = bisect.bisect_right(starts, ms + limit_ns)
        for s, e in steps[lo_i:hi_i]:
            a, b = s - ms, e - me  # offsets that put [ms, me] inside [s, e]
            if a <= b:
                edges += [(a, 1), (b, -1)]
    if not edges:
        trace["offset_ns"] = 0.0
        return 0.0
    edges.sort(key=lambda x: (x[0], -x[1]))
    best, count, lo, hi = -1, 0, 0.0, 0.0
    for i, (x, d) in enumerate(edges):
        count += d
        if d > 0 and count > best:
            best, lo = count, x
            hi = edges[i + 1][0] if i + 1 < len(edges) else x
    off = (lo + hi) / 2
    for dev in trace["devices"].values():
        for key in ("ops", "async", "modules"):
            for ev in dev[key]:
                ev[1] += off
                ev[2] += off
    trace["offset_ns"] = off
    return off


def short(name: str) -> str:
    """``%instr = shape opcode(...)`` -> ``instr (opcode)``."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", rest)
    return f"{head.lstrip('%')} ({m.group(1)})" if m else head.lstrip("%")


def window(trace: dict) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in trace["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("no bench.window span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def busy_ns(dev: dict, lo: float, hi: float) -> float:
    return covered(merge((s, e) for _, s, e in dev["ops"]), lo, hi)


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name.partition(" = ")[2] or name))


def working_ns(dev: dict, lo: float, hi: float) -> float:
    """Busy time less the time in which only collectives run: a chip
    that waits in a ``collective-permute-done`` is not working. (A
    control op such as a ``while`` whose span holds the wait still
    counts as work.)"""
    return covered(merge((s, e) for n, s, e in dev["ops"] if not is_collective(n)), lo, hi)


def exposed_collective_ns(dev: dict, lo: float, hi: float) -> float:
    """Time a collective is in flight (its op, or its async transfer)
    while no other op runs on the chip."""
    coll = merge((s, e) for n, s, e in dev["ops"] + dev["async"] if is_collective(n))
    comp = merge((s, e) for n, s, e in dev["ops"] if not is_collective(n))
    total = 0.0
    for s, e in coll:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total += (e - s) - covered(comp, s, e)
    return total


def time_where(events, pred, lo: float, hi: float) -> tuple[float, int]:
    """Summed in-window duration and count of events whose name
    satisfies ``pred``."""
    t, n = 0.0, 0
    for name, s, e in events:
        if pred(name) and e > lo and s < hi:
            t += min(e, hi) - max(s, lo)
            n += 1
    return t, n


def within(events, outer, lo: float, hi: float) -> list:
    """The events that lie inside one of the ``outer`` events."""
    spans = merge((s, e) for _, s, e in outer)
    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[2] <= spans[i][1] and ev[2] > lo and ev[1] < hi:
            out.append(ev)
    return out


def top_ops(dev: dict, lo: float, hi: float, n: int = 10) -> list:
    by: dict[str, float] = {}
    for name, s, e in dev["ops"]:
        if e > lo and s < hi:
            k = short(name)
            by[k] = by.get(k, 0.0) + (min(e, hi) - max(s, lo))
    return [[k, v / 1e9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host_span(dev: dict, host: list, lo: float, hi: float, n: int = 10) -> list:
    """The device's idle time split by what the host was doing: each
    idle gap's overlap with each benchmark host span (innermost first),
    the rest under 'no span'; summed per span name, the ``n`` largest."""
    idle = gaps(merge((s, e) for _, s, e in dev["ops"]), lo, hi)
    spans = sorted(((s, e, name) for name, s, e in host if name != WINDOW_SPAN),
                   key=lambda x: x[1] - x[0])
    by: dict[str, float] = {}
    for gs, ge in idle:
        free = [(gs, ge)]
        for s, e, name in spans:
            nxt = []
            for a, b in free:
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    by[name] = by.get(name, 0.0) + ov
                    nxt += [(a, max(a, s))] if s > a else []
                    nxt += [(min(b, e), b)] if e < b else []
                else:
                    nxt.append((a, b))
            free = [(a, b) for a, b in nxt if b > a]
        rest = sum(b - a for a, b in free)
        if rest > 0:
            by["no span"] = by.get("no span", 0.0) + rest
    return [[k, v / 1e9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
