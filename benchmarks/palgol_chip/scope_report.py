#!/usr/bin/env python3
"""Device time of a cell's job by Palgol plan item, and what tracing costs.

    python3 benchmarks/palgol_chip/scope_report.py --workload <cell> \\
        --seed <n> [--scale <s>] [--save <dir>]

Builds the cell's graph from ``--seed`` (at ``--scale`` in place of the
configuration's, where given), compiles and warms up its job as
``run.py`` does, then runs the first job twice with the same inputs: once
under the profiler, once not. Prints one JSON line: the set-up spans of
the program (``repro.trace``), both jobs' wall times, the trace's busy
time, the device seconds of every plan item (``scopes.reduce``), their
sum and the unscoped share, the per-trip device time of each leaf inside
the loops, the time outside them, and the fixpoint frontier. ``--save``
keeps the trace and the executable's HLO text there. Results are not
compared with the reference: ``run.py`` does that.

Without a TPU it exits non-zero before any work.
"""

from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path


def report(cell, seed: int, save=None) -> dict:
    """The report of ``cell`` (a ``harness.Cell``) on the default
    device."""
    import jax

    import device
    import harness
    import scopes

    spans = {}

    def listen(event, duration, **kwargs):
        if event.startswith("/palgol/"):
            spans[event] = spans.get(event, 0.0) + duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with device.CompileClock() as clock:
            graph = cell.module("graphs", cell.config["generator"]).build_graph(
                seed, cell.config)
            inputs = harness.job_stream(cell.traffic, graph, seed)(0)[0]
            job = cell.module("placements", cell.traffic["placement"]).prepare(
                cell.program, graph, inputs)
            job.warm(inputs)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    result = cell.traffic["result"]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tdir:
        with jax.profiler.trace(tdir, profiler_options=options):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(harness.JOB_SPAN):
                _, trips, counts, _ = job.run(inputs, result)
            traced_s = time.perf_counter() - t
        (path,) = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        reduced = scopes.reduce(path, harness.JOB_SPAN, job.hlo_text)
        if save is not None:
            save.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, save / "job.xplane.pb")
            (save / "job.hlo.txt").write_text(job.hlo_text)
    t = time.perf_counter()
    _, untraced_trips, _, _ = job.run(inputs, result)
    untraced_s = time.perf_counter() - t
    by_item = reduced["scopes"]
    n_trips = sum(trips)
    scoped = sum(v for k, v in by_item.items() if k != scopes.UNSCOPED)
    unscoped = by_item.get(scopes.UNSCOPED, 0.0)
    busy = reduced["busy_s"]
    sets = counts.get("active_sets")
    return {
        "workload": cell.name, "seed": seed,
        "scale": cell.config["scale"],
        "device": device.describe(jax.devices()[:1]),
        "setup_spans_s": spans, "compile_s": clock.seconds,
        "trips": trips, "untraced_trips": untraced_trips,
        "traced_job_s": traced_s, "untraced_job_s": untraced_s,
        "busy_s": busy, "window_s": reduced["window_s"],
        "scoped_s": scoped, "unscoped_s": unscoped,
        "unscoped_of_busy": unscoped / busy if busy else None,
        "sum_of_busy": (scoped + unscoped) / busy if busy else None,
        "leaf_ms_per_trip": {
            leaf: scopes.leaf_ms_per_trip(by_item, leaf, n_trips)
            for leaf in scopes.LEAVES
        },
        "outside_loops_ms": 1000.0 * (scopes.outside_loops_s(by_item) or 0),
        "frontier": sets,
        "frontier_frac": (
            sum(map(sum, sets)) / (graph.n_vertices * n_trips)
            if sets is not None and n_trips else None
        ),
        "plan_items": scopes.top(by_item, len(by_item)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--scale", type=int)
    ap.add_argument("--save", type=Path)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import run

    cell, _, _ = run.start(args.workload)
    if args.scale is not None:
        cell.config = dict(cell.config, scale=args.scale)
    print(json.dumps(report(cell, args.seed, args.save)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
