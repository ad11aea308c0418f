#!/usr/bin/env python3
"""Rehearse a cell on the CPU at a tiny size, before it goes to the chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <name> --seed 1 --seconds 5 [--trace 1]

Runs ``bench/run.py`` end to end with every width, length and pool of
the cell cut down, the look for a TPU skipped, Pallas kernels in
interpret mode and, for a four-chip cell, four virtual CPU devices. A
number it prints says nothing about the chip, and its ``correct`` holds
tiny widths to limits set for the cell's own size.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path


def shrink(cell: dict, config: dict, mix: dict) -> tuple[dict, dict, dict]:
    """The configuration cut by its architecture's ``tiny()``; the
    cell and its traffic cut here, by the cell's driver."""
    from bench import arch

    config = arch.load(config).module.tiny(config)
    if cell["driver"] == "serve":
        cell = dict(cell, warm_batch=2, warm_ticks=20,
                    engine=dict(cell["engine"], max_batch=4, max_len=256, n_blocks=65),
                    check=dict(cell["check"], requests=8, pad_tokens=192, pad_positions=65))
        mix = dict(mix, clients=4, initial_per_tick=2, requests=64,
                   prompt=dict(mix["prompt"], median=48, min=32, max=128, quantum=16),
                   output=dict(mix["output"], median=16, min=8, max=64),
                   shared_prefix=dict(mix["shared_prefix"], tokens=16))
    else:
        cell = dict(cell, step=dict(cell["step"], wire_chunk_bytes=1 << 16))
        mix = dict(mix, seq=128, rows_per_chip=2, ring=4)
    return cell, config, mix


# stand-in peaks so that a rehearsal exercises the metric readers; the
# numbers they then print mean nothing
shrink.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def main(argv=None, before=None) -> int:
    """``before``, if given, is called once the environment is set and
    before the run starts (bench/tests/fault_run.py plants its fault
    there)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("REPRO_KERNEL_INTERPRET", "1")
    argv = sys.argv[1:] if argv is None else argv
    root = Path(__file__).resolve().parents[1]
    import json

    name = argv[argv.index("--workload") + 1]
    chips = next(w["chips"] for w in json.load(open(root / "BENCHMARK.json"))["workloads"]
                 if w["name"] == name)
    if chips > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={chips}")
    sys.path[:0] = [str(root / "bench"), str(root / "src"), str(root)]
    import run

    if before is not None:
        before()
    return run.main(argv, rehearsal=shrink)


if __name__ == "__main__":
    sys.exit(main())
