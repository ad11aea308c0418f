"""The harness finds every cell's files by name, refuses to run where it
cannot measure, and keeps its peaks table to known chips."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import common  # noqa: E402


def bench_json() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_finds_its_files():
    bj = bench_json()
    configs = {c["name"]: c for c in bj["configs"]}
    for wl in bj["workloads"]:
        assert (ROOT / configs[wl["config"]]["file"]).is_file()
        assert (ROOT / "bench" / "traffic" / f"{wl['traffic']}.json").is_file()
        cell = json.loads((ROOT / "bench" / "cells" / f"{wl['name']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{cell['driver']}.py").is_file()
    for m in bj["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_peaks_refuse_an_unknown_chip():
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("cpu")


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.decode-closed32",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=cwd)


def test_no_tpu_means_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_traffic_is_the_seed_and_only_the_seed():
    """The same seed gives the same requests; another seed gives other
    requests but, over any 100 of them, the same amount of work."""
    from bench import traffic

    mix = json.loads((ROOT / "bench" / "traffic" / "decode-closed32.json").read_text())
    a = traffic.closed_loop_requests(mix, 1000, 3_000_000_019)
    b = traffic.closed_loop_requests(mix, 1000, 3_000_000_019)
    c = traffic.closed_loop_requests(mix, 1000, 2**33 + 5)
    assert all((x.prompt == y.prompt).all() and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))
    assert [len(r.prompt) for r in a[:100]] != [len(r.prompt) for r in c[:100]]
    for lo in range(mix["clients"], len(a) - 100, 100):
        for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            wa = sum(map(field, a[lo:lo + 100]))
            wc = sum(map(field, c[lo:lo + 100]))
            assert abs(wa - wc) < 0.05 * wa
    lens = [len(r.prompt) for r in a]
    assert min(lens) == mix["prompt"]["min"] and max(lens) == mix["prompt"]["max"]
    assert all(n % mix["prompt"]["quantum"] == 0 for n in lens)
