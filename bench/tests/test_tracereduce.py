"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``: five steps of one program holding a matrix
product and a ``paged_decode_attention`` kernel call, each followed by
2 ms of host sleep)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import tracereduce as tr  # noqa: E402
from bench import trace_names as tn  # noqa: E402
from bench.kernels import paged_attention as pa  # noqa: E402

DATA = Path(__file__).parent / "data"
EXPECTED = json.loads((DATA / "fixture_expected.json").read_text())


@pytest.fixture(scope="module")
def trace():
    return tr.load(DATA / "fixture.xplane.pb")


@pytest.fixture(scope="module")
def dev(trace):
    (name,) = trace["devices"]
    assert name == "device:TPU:0"
    return trace["devices"][name]


def test_device_clock_is_moved_onto_the_host_clock(trace, dev):
    """After alignment every program runs inside the host step that
    launched and waited for it; the recorded offset is 1.4-1.7 ms."""
    steps = [(s, e) for n, s, e in trace["host"] if n == "bench.engine_step"]
    assert len(steps) == EXPECTED["steps"] == len(dev["modules"])
    for (_, ms, me), (s, e) in zip(sorted(dev["modules"], key=lambda m: m[1]), sorted(steps)):
        assert s <= ms < me <= e
    assert 1.3e6 < trace["offset_ns"] < 1.8e6


def test_busy_union_matches_a_millisecond_raster(trace, dev):
    lo, hi = tr.window(trace)
    busy = tr.busy_ns(dev, lo, hi)
    # the same union by brute force on a 1 us grid
    grid = set()
    for _, s, e in dev["ops"]:
        grid.update(range(int(max(s, lo) // 1000), int(min(e, hi) // 1000)))
    assert abs(busy / 1000 - len(grid)) <= len(dev["ops"]) + 2
    assert 0 < busy < hi - lo
    assert tr.covered(tr.merge([(0, 2), (1, 3), (5, 6)]), 0, 10) == 4
    assert tr.gaps(tr.merge([(1, 2), (4, 5)]), 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_waiting_in_a_collective_is_not_work():
    """A chip that computes 3 us, then waits 5 us for a transfer and
    computes 1 us more, in a 10 us window: busy 9, working 4, of which
    the exposed collective is the 5 us wait."""
    dev = {"ops": [["%fusion.1 = f32[8] fusion(%p), kind=kLoop", 0, 3000],
                   ["%cp-done.2 = f32[8] collective-permute-done(%cp-start.1)", 3000, 8000],
                   ["%add.3 = f32[8] add(%a, %b)", 8000, 9000]],
           "async": [], "modules": []}
    assert tr.busy_ns(dev, 0, 10000) == 9000
    assert tr.working_ns(dev, 0, 10000) == 4000
    assert tr.exposed_collective_ns(dev, 0, 10000) == 5000


def test_kernel_time_by_name(trace, dev):
    lo, hi = tr.window(trace)
    progs = [m for m in dev["modules"] if EXPECTED["program"] in m[0]]
    kernels = tr.within([op for op in dev["ops"] if tn.is_kernel(op[0])], progs, lo, hi)
    t, n = tr.time_where(kernels, lambda _: True, lo, hi)
    assert n == EXPECTED["kernel_calls"]
    assert t == pytest.approx(sum(e - s for _, s, e in kernels))
    # the kernel is the largest op and named for what it is in the breakdown
    top = tr.top_ops(dev, lo, hi, 3)
    assert top[0][0].endswith("(custom-call)")
    assert top[0][1] == pytest.approx(t / 1e9)


def test_idle_gaps_go_to_the_host_spans(trace, dev):
    lo, hi = tr.window(trace)
    idle = dict(tr.idle_by_host_span(dev, trace["host"], lo, hi))
    total_idle = (hi - lo - tr.busy_ns(dev, lo, hi)) / 1e9
    assert sum(idle.values()) == pytest.approx(total_idle, rel=1e-6)
    # the host slept 2 ms in each bookkeeping span, with the chip idle
    assert idle["bench.bookkeeping"] >= EXPECTED["steps"] * EXPECTED["sleep_s"]
    assert max(idle, key=idle.get) == "bench.bookkeeping"


def test_paged_attention_roofline_arithmetic(trace, dev):
    """The fixture's kernel call: 8 slots of a GQA layer (16 heads of
    128, 2 KV heads) attending 1,024 positions each."""
    cfg = {"model_type": "qwen2", "hidden_size": 2048, "num_attention_heads": 16,
           "num_key_value_heads": 2, "num_hidden_layers": 1}
    flops, nbytes = pa.cost(cfg, [1024] * 8)
    assert flops == 4 * 2048 * 1024 * 8
    assert nbytes == 2 * 256 * 2 * 1024 * 8 + 2 * 2 * 2048 * 8
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert pa.roofline_seconds(flops, nbytes, peaks) == nbytes / 819e9  # bandwidth-bound
    lo, hi = tr.window(trace)
    progs = [m for m in dev["modules"] if EXPECTED["program"] in m[0]]
    kernels = tr.within([op for op in dev["ops"] if tn.is_kernel(op[0])], progs, lo, hi)
    per_call = sum(e - s for _, s, e in kernels) / len(kernels) / 1e9
    share = pa.roofline_seconds(flops, nbytes, peaks) / per_call
    assert 0 < share < 1
