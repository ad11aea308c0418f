"""The readers of the program's own spans (bench/program_spans.py): the
loader on small profiler traces recorded here on the CPU, and the
arithmetic on spans with known times."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import program_spans as ps  # noqa: E402
from bench import tracereduce as tr  # noqa: E402


def _record(out: Path, ticks: int) -> dict:
    """``ticks`` engine-like ticks through repro.obs.trace inside one
    ``bench.window``, under a profiler session; returns what run.py
    would put in its record (the window) and the xplane's path."""
    import jax
    import jax.numpy as jnp

    from repro.obs import trace

    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(out))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(ticks):
                with trace.span("engine.step") as step:
                    with trace.span("engine.admit"):
                        with trace.span("sched.take"):
                            time.sleep(0.001)
                    with trace.span("engine.decode", rows=i + 1):
                        with trace.span("kv.kernel_view"):
                            y = x @ x
                    with trace.span("engine.token_wait"):
                        jax.block_until_ready(y)
                    step.set(rows=i + 1)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = list(out.glob("**/*.xplane.pb"))
    lo, hi = tr.window(tr.load(xplane))
    return {"lo": lo, "hi": hi, "xplane": xplane}


def test_loader_finds_the_run_by_its_window(tmp_path):
    first = _record(tmp_path / "cell.1", 2)
    second = _record(tmp_path / "cell.2", 3)
    for rec, ticks in ((first, 2), (second, 3)):
        spans = ps.for_run(rec, trace_dir=tmp_path)
        assert ps.steps(spans, rec["lo"], rec["hi"]) == ticks
        decodes = ps.in_window(spans, ps.DECODE, rec["lo"], rec["hi"])
        assert [s.args["rows"] for s in decodes] == list(range(1, ticks + 1))
        # nesting by enclosure on one thread
        (step,) = [s for s in spans if s.name == ps.STEP and s.start <= decodes[0].start]
        assert [c.name for c in step.children] == ["engine.admit", ps.DECODE,
                                                    "engine.token_wait"]
        assert [c.name for c in step.children[1].children] == ["kv.kernel_view"]
        assert step.args["rows"] == 1
    assert ps.for_run({"lo": first["lo"], "hi": second["hi"]}, trace_dir=tmp_path) is None
    assert ps.for_run(first, trace_dir=tmp_path / "nothing") is None
    # the sleeps inside sched.take are admission time
    rec = second
    spans = ps.for_run(rec, trace_dir=tmp_path)
    assert ps.admit_ns(spans, rec["lo"], rec["hi"]) >= 3 * 1e6


def _spans():
    """Two ticks on one thread, times in ns. Tick 1 (0-100): admit
    0-30 holding take 0-10 and kv.admit 10-25 (its copy wait 15-20),
    decode 30-60 (rows 3) holding kv.kernel_view 30-40, token wait
    60-90. Tick 2 (100-150): admit 100-105, decode 105-120 (rows 1),
    token wait 120-150."""
    def mk(name, s, e, **args):
        return ps.Span(name, s, e, args)

    spans = [mk("engine.step", 0, 100), mk("engine.admit", 0, 30), mk("sched.take", 0, 10),
             mk("kv.admit", 10, 25), mk("kv.prefix_copy_wait", 15, 20),
             mk("engine.decode", 30, 60, rows=3), mk("kv.kernel_view", 30, 40),
             mk("engine.token_wait", 60, 90),
             mk("engine.step", 100, 150), mk("engine.admit", 100, 105),
             mk("engine.decode", 105, 120, rows=1), mk("engine.token_wait", 120, 150)]
    ps._nest(spans)
    return spans


def test_readers_arithmetic(monkeypatch):
    spans = _spans()
    assert ps.steps(spans, 0, 150) == 2
    # engine.step less its waits, the copy wait nested in kv.admit included
    assert ps.engine_host_ns(spans, 0, 150) == (100 - 30 - 5) + (50 - 30)
    # admit less kv.* (the copy wait inside it goes with it) and waits
    assert ps.admit_ns(spans, 0, 150) == (30 - 15) + 5
    # kv self time: kv.admit less its copy wait, kv.kernel_view whole
    assert ps.kv_host_ns(spans, 0, 150) == (15 - 5) + 10
    assert ps.occupancy(spans, 0, 150, max_batch=4) == pytest.approx(100 * 2 / 4)
    # clipped to the window: only the first tick's decode is in [0, 100)
    assert ps.steps(spans, 0, 100) == 1
    assert ps.engine_host_ns(spans, 0, 100) == 100 - 30 - 5
    assert ps.engine_host_ns(spans, 50, 100) == 50 - 30
    # per step: the reader's ns over the decode steps, in ms
    monkeypatch.setattr(ps, "for_run", lambda rec: spans)
    assert ps.per_step_ms({"lo": 0, "hi": 150}, ps.kv_host_ns) == pytest.approx(20 / 2 / 1e6)
    assert ps.per_step_ms({"lo": 200, "hi": 300}, ps.kv_host_ns) is None


def test_idle_goes_to_the_innermost_span():
    """The device works 0-35 and 95-150: its idle 35-95 splits into
    decode self time (40-60), kv.kernel_view (35-40), the token wait
    (60-90) and the step (90-95); 150-160 has no span open."""
    spans = _spans()
    dev = {"ops": [["a", 0, 35], ["b", 95, 150]]}
    host = [["bench.window", 0, 160], ["bench.engine_step", 0, 150]]
    got = ps.idle_by_span(dev, spans, host, 0, 160)
    assert got == pytest.approx({"engine.token_wait": 30e-9, "engine.decode": 20e-9,
                                 "kv.kernel_view": 5e-9, "engine.step": 5e-9,
                                 "no span": 10e-9})
    assert sum(got.values()) == pytest.approx(60e-9 + 10e-9)


def test_clock_correction_agrees_with_dispatch_and_wait():
    """Three steps 200 ms apart whose decode program was dispatched at
    the end of ``kv.kernel_view`` (10 ms into the step) and started
    0.5 ms later, and whose sample ended 0.5 ms before
    ``engine.token_wait`` did, with the device events 12 ms late: the
    bounds leave -12.5 to -11.5 ms, the correction is their middle, and
    the idle split follows it."""
    ms = 1e6
    spans, mods = [], []
    for k in range(3):
        t = k * 200 * ms
        step = ps.Span("engine.step", t, t + 150 * ms, {})
        dec = ps.Span(ps.DECODE, t + 5 * ms, t + 20 * ms, {"rows": 1})
        view = ps.Span("kv.kernel_view", t + 5 * ms, t + 10 * ms, {})
        wait = ps.Span("engine.token_wait", t + 20 * ms, t + 140 * ms, {})
        spans += [step, dec, view, wait]
        late = 12 * ms
        mods += [["jit_decode_step_paged(1)", t + 10.5 * ms + late, t + 120 * ms + late],
                 ["jit_sample_last(2)", t + 120 * ms + late, t + 139.5 * ms + late]]
    ps._nest(spans)
    dev = {"modules": mods, "ops": mods}
    c = ps.clock_correction(dev, spans)
    assert c == pytest.approx(-12 * ms)
    # device work 10.5-139.5 ms of each step after the correction: the
    # idle before it lies in the step's own spans, none in token_wait
    got = ps.idle_by_span(dev, spans, [], 0, 150 * ms, shift=c)
    assert got.get("engine.token_wait", 0.0) < 1e-3
    assert ps.clock_correction({"modules": [], "ops": []}, spans) == 0.0
