#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name: its entry in ``BENCHMARK.json`` names the
configuration (``bench/configs/<config>.json``) and the traffic mix
(``bench/traffic/<traffic>.json``); ``bench/cells/<name>.json`` holds
the rest (which driver runs it, engine or mesh sizes, warm-up, limits).
With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of the window's first
``trace_seconds`` is reduced by ``bench/metrics/<metric>.py``, one
reader per per-layer metric.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``, each compared number beside its
limit; stderr ends with the same checks. Without a TPU, with another
number of chips than the cell asks for, or without the program's
sources in the checkout, it exits non-zero and prints no result.

``--control 1`` (never used by the benchmark's own runs) reads the
cell's control as well: the reference computed one precision step
below the configuration's, put in the program's place.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Ctx:
    """Everything a driver needs for one run."""

    jax: object
    seed: int
    seconds: float
    control: bool
    chips: int
    cell: dict
    config: dict
    mix: dict
    compile_log: object
    t_start: float
    tracer: object = None
    trace_seconds: float = 0.0


class Tracer:
    """A profiler trace of part of the window, bracketed by a
    ``bench.window`` host span."""

    def __init__(self, jax, directory: Path):
        self.jax, self.dir, self.running, self.span = jax, directory, False, None

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self.jax.profiler.start_trace(str(self.dir))
        self.span = self.jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.running = True

    def stop(self) -> None:
        self.span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.running = False

    def xplane(self) -> Path:
        found = sorted(self.dir.glob("**/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return found[-1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_files(bench_json: dict, name: str, bench_dir: Path) -> tuple[dict, dict, dict, dict]:
    from bench.common import load_json

    wl = next((w for w in bench_json["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench_json["configs"] if c["name"] == wl["config"])
    return (wl, load_json(bench_dir / "cells" / f"{name}.json"), load_json(ROOT / conf["file"]),
            load_json(bench_dir / "traffic" / f"{wl['traffic']}.json"))


def reported(bench_json: dict, name: str, group: str) -> list[dict]:
    """The metrics of ``group`` this cell reports."""
    e2e_here = {m["name"] for m in bench_json["end_to_end"]
                if name in m.get("workloads", [name])}
    out = []
    for m in bench_json[group]:
        if "workloads" in m:
            if name in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def read_metric(name: str, rec: dict):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{len(name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def main(argv=None, rehearsal=None) -> int:
    """``rehearsal``, given only by bench/rehearse.py, shrinks the cell
    and skips the look for a chip."""
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("the program (src/repro) is not in this checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import common, tracereduce

    bench_json = common.benchmark_json()
    wl, cell, config, mix = cell_files(bench_json, args.workload, ROOT / "bench")
    if rehearsal is not None:
        cell, config, mix = rehearsal(cell, config, mix)
    import jax

    common.use_compile_cache(jax)
    chips = wl["chips"]
    if rehearsal is None:
        # a training control runs the reference alone, on one chip
        common.require_tpu(jax, 1 if (args.control and cell["driver"] == "train") else chips)
    log = common.CompileLog(jax)
    tracer = None
    if args.trace:
        tracer = Tracer(jax, ROOT / ".bench_trace" / f"{args.workload}.{args.seed}")
    ctx = Ctx(jax=jax, seed=args.seed, seconds=args.seconds,
              control=bool(args.control), chips=chips, cell=cell,
              config=config, mix=mix, compile_log=log, t_start=T_START, tracer=tracer,
              trace_seconds=min(args.seconds, cell["trace_seconds"]))
    driver = importlib.import_module(f"bench.drivers.{cell['driver']}")
    result, checks, record = driver.run(ctx)
    # every end-to-end number the driver took, the check's readings and
    # what compiled in the window, ahead of the check lines on stderr
    print("run " + json.dumps({
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "readings": record.get("readings"), "compiles_in_window": record.get("compiles_in_window"),
        "window_compiled": [n[:160] for n in record.get("window_compiled", [])]},
        default=str), file=sys.stderr)

    if args.trace:
        trace = tracereduce.load(tracer.xplane())
        lo, hi = tracereduce.window(trace)
        dev_names = sorted(trace["devices"])[:chips]
        if not dev_names:
            raise RuntimeError("the trace holds no TPU plane")
        rec = dict(record, trace=trace, lo=lo, hi=hi, config=config, cell=cell, mix=mix,
                   chips=chips, peaks=(common.peaks(result["device"]["kind"]) if rehearsal is None
                                       else rehearsal.peaks))
        busy = [tracereduce.busy_ns(trace["devices"][d], lo, hi) for d in dev_names]
        metrics = {}
        for m in reported(bench_json, args.workload, "per_layer"):
            value = read_metric(m["name"], rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"]["busy_s"] = sum(busy) / len(busy) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        idlest = dev_names[min(range(len(busy)), key=lambda i: busy[i])]
        result["breakdown"] = {
            "device_ops": tracereduce.top_ops(trace["devices"][dev_names[0]], lo, hi),
            "idle_gaps": tracereduce.idle_by_host_span(trace["devices"][idlest],
                                                       trace["host"], lo, hi)}
        shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        names = {m["name"] for m in reported(bench_json, args.workload, "end_to_end")}
        result["metrics"] = {k: v for k, v in result["metrics"].items() if k in names}
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
