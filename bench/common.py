"""What every cell shares: where the files are, the chip check, the
compile log, the persistent compile cache, the peaks table and the
result line."""
from __future__ import annotations

import json
import logging
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def use_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names another. Every
    program is kept, however quick to compile, so that a second run
    loads all of them."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(ROOT / ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog(logging.Handler):
    """Counts backend compiles (persistent-cache loads included) and
    their seconds through jax.monitoring; ``window`` counts those made
    while ``in_window`` is set, and ``window_names`` keeps what JAX's
    compile log said about each."""

    def __init__(self, jax):
        super().__init__(logging.WARNING)
        self.jax = jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.window = 0
        self.window_names: list[str] = []
        self._in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        logging.getLogger("jax").addHandler(self)

    @property
    def in_window(self) -> bool:
        return self._in_window

    @in_window.setter
    def in_window(self, on: bool) -> None:
        self._in_window = on
        self.jax.config.update("jax_log_compiles", on)

    def emit(self, record) -> None:
        msg = record.getMessage()
        if self._in_window and msg.startswith("Compiling "):
            self.window_names.append(msg[:600])

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs
            if self.in_window:
                self.window += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require_tpu(jax, chips: int):
    """The first device must be a TPU and JAX must see exactly ``chips``
    of them; the Pallas kernels must be compiled, not interpreted."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(jax.devices()) != chips:
        raise SystemExit(f"expected {chips} chips, JAX sees {len(jax.devices())}")
    if os.environ.get("REPRO_KERNEL_INTERPRET", "").strip().lower() in ("1", "true", "yes", "on"):
        raise SystemExit("REPRO_KERNEL_INTERPRET forces the Pallas interpreter")
    return dev


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def device_record(devices) -> dict:
    """The devices as JAX reports them, with the peak memory in use on
    the fullest."""
    dev = devices[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    stderr, then the result line, with the checks under the last key,
    as the last line of stdout."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
