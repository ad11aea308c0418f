"""TraceGraph (DESIGN.md §16): tracer ring/lifecycle invariants, the
always-on metrics registry, Chrome trace export + schema validation,
bitwise identity with tracing disabled, compile-gating helpers, and
end-to-end span lifecycles across every engine mode including the
FaultFleet recovery arms."""
import dataclasses
import json

import numpy as np
import pytest

from repro.obs import export, registry, trace
from repro.serve.engine import Request


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Tracing is opt-in per test; never leak a tracer into the suite."""
    trace.disable()
    yield
    trace.disable()


# -- metrics registry -----------------------------------------------------------


def test_counter_gauge_create_on_use_and_type_conflict():
    reg = registry.MetricsRegistry()
    c = reg.counter("x")
    c.inc()
    c.inc(2)
    assert reg.counter("x") is c and c.value == 3
    g = reg.gauge("g")
    g.set(1.5)
    g.set(2.5)
    assert reg.gauge("g").value == 2.5
    with pytest.raises(TypeError):
        reg.gauge("x")  # re-registering under a different type


def test_histogram_buckets_and_percentiles():
    h = registry.Histogram("h", bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count == 4 and h.counts == [1, 1, 1, 1]
    assert h.total == 105.0
    assert h.percentile(0.5) == 2.0  # bucket-upper-bound estimate
    assert h.percentile(0.99) == 4.0  # overflow clamps to top boundary
    assert registry.Histogram("empty").percentile(0.5) == 0.0
    with pytest.raises(ValueError):
        registry.Histogram("bad", bounds=(2.0, 1.0))


def test_histogram_merge_matches_single_stream():
    vals = [float(v) for v in
            np.random.default_rng(0).integers(1, 5000, size=200)]
    one = registry.Histogram("lat")
    a, b = registry.Histogram("lat"), registry.Histogram("lat")
    for i, v in enumerate(vals):
        one.observe(v)
        (a if i % 2 else b).observe(v)
    a.merge(b)
    assert a.counts == one.counts and a.count == one.count
    assert a.total == one.total
    for q in (0.5, 0.9, 0.99):
        assert a.percentile(q) == one.percentile(q)  # shard-invariant
    with pytest.raises(ValueError):
        a.merge(registry.Histogram("other", bounds=(1.0, 2.0)))


def test_registry_merge_and_in_place_reset():
    reg = registry.MetricsRegistry()
    c = reg.counter("n")
    c.inc(5)
    other = registry.MetricsRegistry()
    other.counter("n").inc(2)
    other.gauge("g").set(7.0)
    other.histogram("h").observe(3.0)
    reg.merge(other)
    assert reg.counter("n").value == 7
    assert reg.gauge("g").value == 7.0
    assert reg.histogram("h").count == 1
    reg.reset()
    assert reg.counter("n").value == 0
    c.inc()  # the cached reference is still the live metric
    assert reg.snapshot()["n"] == 1


def test_snapshot_is_json_and_never_uses_bench_wall_keys():
    reg = registry.MetricsRegistry()
    reg.counter("serve.ticks").inc(3)
    reg.gauge("fleet.rows").set(8.0)
    reg.histogram("serve.latency_ticks").observe(12.0)
    snap = reg.snapshot()
    json.dumps(snap)

    wall = {"seconds", "wall_s", "total_s"}  # run.py's collect_walls leaves

    def no_wall_keys(node):
        if isinstance(node, dict):
            assert not wall & set(node), f"wall-key collision in {sorted(node)}"
            for v in node.values():
                no_wall_keys(v)

    no_wall_keys(snap)


def test_publish_kv_stats_sets_known_gauges_only():
    registry.reset()
    registry.publish_kv_stats(
        {"blocks_in_use": 3, "prefix_hits": 7, "unknown_key": 9})
    reg = registry.get_registry()
    assert reg.gauge("kv.blocks_in_use").value == 3.0
    assert reg.gauge("kv.prefix_hits").value == 7.0
    assert "kv.unknown_key" not in reg.snapshot()


# -- tracer ---------------------------------------------------------------------


def test_disabled_tracer_is_one_null_singleton():
    assert not trace.enabled() and trace.get() is None
    s = trace.span("x", ("p", "t"))
    assert s is trace.span("y")  # one cached null context manager
    with s:
        pass
    # every module-level emit is a no-op branch
    trace.begin("a")
    trace.end()
    trace.complete("c", 0.1)
    trace.instant("i")
    trace.counter("n", {"v": 1.0})
    trace.request_begin(0)
    trace.request_mark(0, "hop")
    trace.request_end(0)
    assert trace.get() is None


def _profiler_spans(trace_dir, prefixes=("engine.", "kv.", "sched.")) -> list[dict]:
    """The host spans a profiler session wrote under ``trace_dir``
    whose names start with ``prefixes``, with their thread and stats."""
    from jax.profiler import ProfileData

    (path,) = list(trace_dir.glob("**/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                out += [{"name": e.name, "start": e.start_ns, "end": e.end_ns,
                         "thread": (plane.name, k), "args": dict(e.stats)}
                        for e in line.events if e.name.startswith(prefixes)]
    return sorted(out, key=lambda e: e["start"])


def test_span_reaches_the_profiler_only_while_a_session_runs(tmp_path):
    """With no tracer and no profiler session ``span()`` is the null
    singleton; inside a session it writes a TraceAnnotation of the same
    name carrying its attributes, those set at its end included."""
    import jax

    assert trace.span("x") is trace.span("y")
    jax.profiler.start_trace(str(tmp_path))
    try:
        s = trace.span("obs.test", ("p", "t"), rows=3)
        assert s is not trace.span("y")
        with s as sp:
            sp.set(finished=1)
    finally:
        jax.profiler.stop_trace()
    assert trace.span("x") is trace.span("y")
    (ev,) = _profiler_spans(tmp_path, ("obs.",))
    assert ev["name"] == "obs.test" and ev["args"] == {"rows": 3, "finished": 1}
    assert trace.get() is None  # the session installs no ring tracer


def test_ring_buffer_bounds_events_and_counts_drops():
    t = trace.enable(capacity=8)
    for _ in range(20):
        t.instant("e")
    assert len(t.events) == 8 and t.dropped == 12


def test_span_nesting_and_lifecycle_guards():
    t = trace.enable()
    tr = ("p", "t1")
    with t.span("outer", tr, depth=1):
        with t.span("inner", tr):
            assert t.open_depth(tr) == 2
    assert t.open_depth(tr) == 0
    t.request_begin(7, tenant="a")
    t.request_begin(7)  # re-queue after a fault: guarded, not a new span
    t.request_mark(7, "hop", ("p", "t1"))
    t.request_end(7)
    t.request_end(7)  # guarded
    life = t.lifecycle_report()
    assert life["begins"] == 1 and life["ends"] == 1
    assert life["double_begins"] == 1 and life["double_ends"] == 1
    assert life["open"] == []


def test_lifecycle_counters_survive_ring_wrap():
    t = trace.enable(capacity=4)
    for uid in range(10):
        t.request_begin(uid)
        t.request_end(uid)
    life = t.lifecycle_report()
    assert life["begins"] == life["ends"] == 10
    assert life["open"] == [] and t.dropped > 0


# -- export + schema validation -------------------------------------------------


def test_chrome_trace_export_validates_and_carries_metrics():
    t = trace.enable()
    with t.span("work", ("engine", "prefill"), uid=1):
        t.instant("marker", ("engine", "prefill"))
    t.counter("kv", {"blocks": 2.0}, ("engine", "decode"))
    t.complete("tick", 1e-3, ("fleet", "control"), tick=0)
    t.request_begin(1)
    t.request_mark(1, "hop", ("engine", "decode"))
    t.request_end(1)
    obj = export.chrome_trace(metrics={"serve.ticks": 3})
    assert export.validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    names = {e.get("name") for e in evs}
    assert {"work", "marker", "kv", "tick", "request", "hop"} <= names
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"engine", "fleet", "requests"} <= procs
    flows = [e for e in evs if e.get("cat") == "flow"]
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    assert all(e["id"] == 1 for e in flows)
    assert obj["otherData"]["metrics"] == {"serve.ticks": 3}
    json.dumps(obj)


def test_validator_flags_broken_traces():
    t = trace.enable()
    t.request_begin(5)  # start without finish
    errs = export.validate_chrome_trace(export.chrome_trace())
    assert any("start without finish" in e for e in errs)
    assert any("unknown phase" in e for e in export.validate_chrome_trace(
        {"traceEvents": [{"ph": "Z", "pid": 1, "tid": 1, "ts": 0.0}]}))
    assert export.validate_chrome_trace({"traceEvents": None})
    assert any("missing" in e for e in export.validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "pid": 1, "tid": 1, "ts": 0.0}]}))
    with pytest.raises(ValueError):
        export.assert_valid_chrome_trace({"traceEvents": None})


def test_chrome_trace_requires_a_tracer():
    with pytest.raises(ValueError):
        export.chrome_trace()


# -- compile gating (core/adapt.py satellites) ----------------------------------


def test_compile_gate_skips_first_sample_and_marks_trace():
    from repro.core.adapt import CompileGate

    t = trace.enable()
    g = CompileGate()
    assert g.sample(0.5) is False  # post-build sample: polluted by jit
    assert g.sample(0.1) is True
    assert g.sample(0.1) is True
    g.rebuilt()
    assert g.sample(0.2) is False
    assert [e["name"] for e in t.events] == ["compile", "compile"]


def test_warmed_step_builds_once_and_traces_compile():
    import jax
    import jax.numpy as jnp

    from repro.core.adapt import warmed_step

    t = trace.enable()
    cache: dict = {}
    built = []

    def build():
        built.append(1)
        return jax.jit(lambda x: x + 1)

    fn = warmed_step(cache, ("k", 2), build, jnp.zeros(2))
    fn2 = warmed_step(cache, ("k", 2), build, jnp.zeros(2))
    assert fn is fn2 and built == [1]
    np.testing.assert_array_equal(np.asarray(fn(jnp.zeros(2))), np.ones(2))
    spans = [e for e in t.events if e.get("name") == "compile"]
    assert [e["ph"] for e in spans] == ["B"]  # one warm, one span begin
    assert sum(e["ph"] == "E" for e in t.events) == 1


# -- engine-level lifecycle invariants ------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import build

    cfg = dataclasses.replace(get_smoke("tinyllama-1.1b"), dtype=jnp.float32)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n=6, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(3, 8))).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def _drain(eng, cap=500):
    while not eng.idle():
        eng.step()
        cap -= 1
        assert cap > 0, "engine did not drain"


@pytest.mark.parametrize("kind", ["aligned", "continuous_paged",
                                  "disagg_aligned", "disagg", "fleet",
                                  "fleet_paged"])
def test_engine_lifecycle_invariants(tiny_model, kind):
    """Every engine mode closes exactly one lifecycle span per accepted
    request — begins == ends, nothing open after drain, no doubles —
    and the exported trace passes the schema gate (all flows resolve)."""
    from repro.serve import DisaggConfig, EngineConfig, KVSpec, make_engine
    from repro.serve.fleet import FleetConfig

    cfg, model, params = tiny_model
    t = trace.enable()
    paged = KVSpec(kind="paged", block_size=4, prefix_cache=True)
    if kind == "aligned":
        ecfg = EngineConfig(max_batch=4, max_len=64)
    elif kind == "continuous_paged":
        ecfg = EngineConfig(max_batch=4, max_len=64, mode="continuous",
                            kv=paged)
    elif kind == "disagg_aligned":
        ecfg = DisaggConfig(n_prefill_rows=2, decode_slots=4, max_len=64)
    elif kind == "disagg":
        ecfg = DisaggConfig(n_prefill_rows=2, decode_slots=4, max_len=64,
                            mode="continuous")
    elif kind == "fleet":
        ecfg = FleetConfig(mode="continuous", n_rows=4, prefill_rows=1,
                           slots_per_row=2, max_len=64, prefill_chunk=16)
    else:
        ecfg = FleetConfig(mode="continuous", n_rows=4, prefill_rows=1,
                           slots_per_row=2, max_len=64, prefill_chunk=16,
                           kv=paged)
    eng = make_engine(model, params, ecfg)
    reqs = _requests(cfg)
    accepted = sum(bool(eng.submit(r)) for r in reqs)
    assert accepted == len(reqs)
    _drain(eng)
    life = t.lifecycle_report()
    assert life["begins"] == life["ends"] == accepted
    assert life["open"] == []
    assert life["double_begins"] == 0 and life["double_ends"] == 0
    names = {e.get("name") for e in t.events}
    assert "retire" in names
    if kind in ("disagg", "fleet"):
        assert "handoff" in names or "handoff:prefix_hit" in names
    obj = export.chrome_trace()
    assert export.validate_chrome_trace(obj) == []


def test_spec_engine_lifecycle_invariants(tiny_model):
    from repro.serve import SpecConfig, make_engine

    cfg, model, params = tiny_model
    t = trace.enable()
    eng = make_engine(
        model, params,
        SpecConfig(max_batch=4, max_len=64, spec_k=4),
        draft=(model, params),  # self-draft: 100% acceptance, still spec
    )
    reqs = _requests(cfg)
    for r in reqs:
        assert eng.submit(r)
    _drain(eng)
    life = t.lifecycle_report()
    assert life["begins"] == life["ends"] == len(reqs)
    assert life["open"] == []
    assert life["double_begins"] == 0 and life["double_ends"] == 0
    names = {e.get("name") for e in t.events}
    assert {"draft", "verify", "verdict"} <= names
    assert export.validate_chrome_trace(export.chrome_trace()) == []


@pytest.mark.parametrize("arm", ["retry", "preempt", "checkpoint"])
def test_fault_recovery_keeps_one_lifecycle_span(tiny_model, arm, tmp_path):
    """Recovery re-queues route through sched.submit, so a faulted
    request keeps its ONE lifecycle span open across the retry/restore —
    the trace never double-begins, and every span still closes."""
    from repro.serve.faults import FaultEvent
    from repro.serve.fleet import FleetConfig, FleetEngine

    cfg, model, params = tiny_model
    t = trace.enable()
    kw = dict(mode="continuous", n_rows=4, prefill_rows=1, slots_per_row=2,
              max_len=64, prefill_chunk=16, min_rows=2)
    if arm == "checkpoint":
        kw.update(recovery="checkpoint", ckpt_dir=str(tmp_path / "ck"),
                  ckpt_cadence=1)
    fe = FleetEngine(model, params, FleetConfig(**kw))
    n = 8
    for i in range(n):
        rng = np.random.default_rng(i)
        fe.submit(Request(
            uid=i, prompt=rng.integers(0, 100, 5 + (i % 3)).astype(np.int32),
            max_new_tokens=8))
    spr = fe.cfg.slots_per_row
    for _ in range(30):  # fill the tail slots a row loss will kill
        fe.step()
        if all(s is not None for s in fe.eng.slots[-spr:]):
            break
    else:
        raise AssertionError("tail decode slots never filled")
    kind = "preempt" if arm == "preempt" else "device_loss"
    extra = {"duration": 4} if arm == "preempt" else {}
    fe.inject_fault(FaultEvent(fe.eng.tick + 1, kind, rows=1, **extra))
    fe.drain()
    if fe.ckpt is not None:
        fe.ckpt.close()
    life = t.lifecycle_report()
    assert life["begins"] == life["ends"] == n
    assert life["open"] == []
    assert life["double_begins"] == 0 and life["double_ends"] == 0
    names = {e.get("name") for e in t.events}
    assert "fault" in names
    if arm == "retry":
        assert fe.recoveries["retried"] >= 1 and "retry" in names
    elif arm == "preempt":
        assert fe.recoveries["staged"] >= 1 and "regrow" in names
    else:
        assert fe.recoveries["restored"] >= 1
        assert "checkpoint_restore" in names and "checkpoint_save" in names
    assert export.validate_chrome_trace(export.chrome_trace()) == []


def test_tracing_disabled_outputs_bitwise_identical(tiny_model, tmp_path):
    """Observation never perturbs: the same workload with the tracer off,
    then on, then with a profiler session instead yields bit-identical
    logits every tick and identical output streams (instrumentation is
    host-side only — no added, reordered, or synchronized device
    work)."""
    import jax

    from repro.serve import EngineConfig, KVSpec, make_engine

    cfg, model, params = tiny_model

    def run():
        eng = make_engine(model, params, EngineConfig(
            max_batch=4, max_len=64, mode="continuous",
            kv=KVSpec(kind="paged", block_size=4, prefix_cache=True)))
        for r in _requests(cfg):
            eng.submit(r)
        logits = []
        steps = 0
        while not eng.idle():
            eng.step()
            logits.append(np.asarray(eng.last_logits).copy())
            steps += 1
            assert steps < 500
        return {r.uid: list(r.out_tokens) for r in eng.finished}, logits

    assert not trace.enabled()
    streams_off, logits_off = run()
    trace.enable()
    streams_on, logits_on = run()
    trace.disable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        streams_prof, logits_prof = run()
    finally:
        jax.profiler.stop_trace()
    assert _profiler_spans(tmp_path)  # the session did see the spans
    for streams, logits in ((streams_on, logits_on), (streams_prof, logits_prof)):
        assert streams == streams_off
        assert len(logits) == len(logits_off)
        for a, b in zip(logits_off, logits):
            np.testing.assert_array_equal(a, b)


def test_profiler_session_records_engine_spans(tiny_model, tmp_path):
    """A profiler session around a continuous paged engine records the
    serving spans on the host: two admissions and one decode per tick,
    ``kv.kernel_view`` inside ``engine.decode`` inside ``engine.step``,
    ``rows`` equal to the active slots, and the request's ``uid`` on the
    KV store's admissions (a repeated prompt skips prefill)."""
    import jax

    from repro.serve import EngineConfig, KVSpec, make_engine

    cfg, model, params = tiny_model
    eng = make_engine(model, params, EngineConfig(
        max_batch=4, max_len=64, mode="continuous",
        kv=KVSpec(kind="paged", block_size=4, prefix_cache=True)))
    reqs = _requests(cfg)
    reqs.append(Request(uid=len(reqs), prompt=reqs[0].prompt.copy(), max_new_tokens=4))
    for r in reqs:
        assert eng.submit(r)
    batches = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        while not eng.idle():
            eng.step()
            batches.append(eng.last_tick["decode_batch"])
            assert len(batches) < 500
    finally:
        jax.profiler.stop_trace()
    assert "kv" not in eng.last_tick  # per-tick KV stats only when traced
    spans = _profiler_spans(tmp_path)

    def named(name):
        return [e for e in spans if e["name"] == name]

    def inside(inner, outer):
        return (inner["thread"] == outer["thread"]
                and outer["start"] <= inner["start"] and inner["end"] <= outer["end"])

    steps, decodes = named("engine.step"), named("engine.decode")
    assert len(steps) == len(batches)
    assert len(named("engine.admit")) == 2 * len(batches)
    assert [e["args"]["rows"] for e in decodes] == [b for b in batches if b]
    assert [e["args"]["rows"] for e in steps] == batches
    assert len(named("engine.token_wait")) == len(decodes) == len(named("engine.retire"))
    for view in named("kv.kernel_view"):
        (dec,) = [d for d in decodes if inside(view, d)]
        assert any(inside(dec, st) for st in steps)
        # each active slot holds at least its first block; 4 slots x 16
        assert dec["args"]["rows"] <= view["args"]["live_blocks"] <= 64
        assert view["args"]["table_blocks"] == 64
    assert sum(e["args"]["finished"] for e in named("engine.retire")) == len(reqs)
    cold = sorted(e["args"]["uid"] for e in named("kv.admit"))
    assert cold == list(range(len(reqs) - 1))
    assert [e["args"]["uid"] for e in named("kv.admit_from_full")] == [len(reqs) - 1]
    for wait in ("kv.prefix_copy_wait", "engine.first_token_wait"):
        assert sorted(e["args"]["uid"] for e in named(wait)) == cold
    assert sum(e["args"]["tokens"] for e in named("engine.prefill")) == sum(
        int(r.prompt.shape[0]) for r in reqs[:-1])
    for take in named("sched.take"):
        assert any(inside(take, a) for a in named("engine.admit"))


def test_kernel_view_span_counts_live_blocks(tiny_model):
    """``kv.kernel_view`` carries the blocks the paged decode kernel
    walks, ceil(pos / block_size) over the active slots, beside the
    size of the whole tables."""
    import jax.numpy as jnp

    from repro.serve import KVSpec, make_kvstore

    cfg, model, params = tiny_model
    kv = make_kvstore(model, 3, 32, KVSpec(kind="paged", block_size=8, n_blocks=13),
                      ragged=True)
    for slot, n in enumerate((5, 16, 20)):
        cache = model.init_cache(1, n)
        cache["pos"] = jnp.int32(n)
        kv.admit(slot, cache, n)
    t = trace.enable()
    kv.kernel_view([0, 2])
    kv.kernel_view([1])
    ends = [e for e in t.events if e["ph"] == "E"]
    assert [e["args"] for e in ends] == [{"live_blocks": 1 + 3, "table_blocks": 12},
                                         {"live_blocks": 2, "table_blocks": 12}]


def test_fleet_trace_schema(tiny_model, tmp_path):
    """The fig13-style acceptance trace: a closed-loop fleet under the
    bursty surge with a mid-run device loss exports valid Chrome JSON
    with per-stage spans, flow-linked request lifecycles, at least one
    replan instant and at least one fault instant."""
    from repro.core.adapt import AdaptPolicy
    from repro.serve.faults import FaultEvent
    from repro.serve.fleet import FleetConfig, FleetEngine
    from repro.serve.sched import FleetScheduler
    from repro.serve.traffic import replay, scenario

    cfg, model, params = tiny_model
    t = trace.enable()
    registry.reset()
    sc = scenario("bursty-multitenant")
    sc = dataclasses.replace(
        sc, horizon=30, max_prompt=56,
        tenants=tuple(dataclasses.replace(t_, surge_at=10)
                      if t_.surge_at >= 0 else t_ for t_ in sc.tenants))

    def clock(tick):
        pre = max(tick["prefill_tokens_per_row"], default=0)
        return max(float(pre), 2.0 * tick["decode_batch"] / 3.0, 1.0) * 1e-3

    fc = FleetConfig(mode="continuous", n_rows=8, prefill_rows=2,
                     slots_per_row=2, max_len=96, prefill_chunk=8,
                     adapt=AdaptPolicy(window=3, cooldown=3,
                                       speedup_threshold=1.05, row_budget=5),
                     prefill_cost_ratio=0.5, prefill_bytes_per_token=64.0)
    fe = FleetEngine(model, params, fc, sched=FleetScheduler(sc.tenants),
                     clock=clock)

    injected = []

    def on_tick(e):
        # lose a row only after the loop has replanned at least once,
        # so the trace is guaranteed to carry both marker kinds
        if not injected and e.regroups >= 1:
            e.inject_fault(FaultEvent(e.eng.tick + 1, "device_loss", rows=1))
            injected.append(e.eng.tick + 1)

    pairs = replay(fe, sc, cfg.vocab_size, max_ticks=2000, on_tick=on_tick)
    assert injected, "closed loop never regrouped — scenario drifted"
    assert len(fe.finished) == len(pairs)  # zero lost through the fault

    snap = registry.get_registry().snapshot()
    path = str(tmp_path / "fleet_trace.json")
    export.write_trace(path, metrics=snap)
    with open(path) as f:
        obj = json.load(f)
    assert export.validate_chrome_trace(obj) == []

    evs = obj["traceEvents"]
    instants = {e["name"] for e in evs if e.get("ph") == "i"}
    assert "replan" in instants and "fault" in instants
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"prefill", "decode", "fleet", "requests"} <= procs
    # per-stage spans: prefill B/E pairs and the per-tick fleet X series
    assert any(e.get("ph") == "B" and e.get("name", "").startswith("prefill")
               for e in evs)
    assert any(e.get("ph") == "X" and e.get("name") == "tick" for e in evs)
    # flow-linked lifecycles: one start and one finish per completion
    starts = sum(e.get("ph") == "s" for e in evs)
    finishes = sum(e.get("ph") == "f" for e in evs)
    assert starts == finishes == len(fe.finished)
    life = obj["otherData"]["lifecycle"]
    assert life["begins"] == life["ends"] and life["open"] == []
    assert snap["fleet.replans"] >= 1
    assert snap["fleet.faults.device_loss"] == 1
    assert snap["serve.completions"] == len(pairs)
