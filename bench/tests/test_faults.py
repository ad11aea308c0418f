"""The harness's ``correct`` comes out false when the timed path is
broken, and when the control is put in the program's place.

Each case drives a whole run through ``bench/tests/fault_run.py`` (CPU,
tiny sizes, the look for a chip skipped, four virtual devices for the
training cell) with one fault planted underneath, and reads the result
line against the cells' own limits. Run from the repository root:

    python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SERVE = "qwen2.5-3b.decode-closed32"
TRAIN = "qwen1.5-0.5b.train-decoupled4"


def run_cell(fault: str, workload: str, *extra: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_KERNEL_INTERPRET="1")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "fault_run.py"), fault,
         "--workload", workload, "--seed", "4000000007", "--seconds", "4", *extra],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_serving_run_is_correct():
    assert run_cell("none", SERVE)["correct"] is True


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged_serve", "half_batch_serve"])
def test_serving_fault_is_caught(fault):
    out = run_cell(fault, SERVE)
    assert out["correct"] is False, out["checks"]


def test_serving_control_is_caught():
    out = run_cell("none", SERVE, "--control", "1")
    assert out["correct"] is False, out["checks"]


@pytest.fixture(scope="module")
def sound_training():
    """At these tiny widths the bf16 program's own readings already sit
    near the limits set at the cell's size on the chip, so a fault has
    to stand out against them, not only against the limits."""
    return run_cell("none", TRAIN)["checks"]


def _stands_out(out: dict, sound: dict, factor: float) -> bool:
    return any(out["checks"][k]["value"] > max(out["checks"][k]["limit"],
                                               factor * sound[k]["value"]) for k in sound)


@pytest.mark.parametrize("fault", ["state_unchanged_train", "half_batch_train", "no_exchange",
                                   "frozen_leaf"])
def test_training_fault_is_caught(fault, sound_training):
    out = run_cell(fault, TRAIN)
    assert out["correct"] is False, out["checks"]
    assert _stands_out(out, sound_training, 10.0), (out["checks"], sound_training)


def test_training_control_is_caught(sound_training):
    out = run_cell("none", TRAIN, "--control", "1")
    assert out["correct"] is False, out["checks"]
    assert _stands_out(out, sound_training, 3.0), (out["checks"], sound_training)
