#!/usr/bin/env python3
"""On-chip benchmark of Palgol jobs on device-built Graph500 graphs.

    python3 benchmarks/palgol_chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process, on the chips it
asks for: builds the cell's graph on the device from ``--seed``, compiles
and warms up its program (set-up), runs jobs back to back for
``--seconds`` (``--trace 0``) or profiles one whole job (``--trace 1``),
compares every job with the plain reference, and prints the result as one
JSON line, the last line of standard output. The compared numbers and
their limits are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any work and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]


def start(workload: str):
    """Everything before the cell's own work: the device check (before
    anything else that touches the chip), the persistent compilation
    cache and the cell's files. Returns ``(cell, devices, peaks)``."""
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}
    if workload not in chips:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    sys.path[:0] = [str(ROOT), str(CHECKOUT / "src")]
    # libtpu would log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import device

    devices = device.tpu_devices(chips[workload])
    peaks = device.peaks(devices[0].device_kind, ROOT / "peaks.json")

    import harness
    from repro import compile_cache

    compile_cache.enable()
    # cache every executable, however quick to compile, so that a run
    # after a checkout's first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return harness.load_cell(workload, spec), devices, peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, devices, peaks = start(args.workload)
    import harness

    line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       devices, T_START, peaks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
