#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/palgol_chip/control.py --workload <cell> \\
        --seeds 1,2,3 [--control-seeds 4,5,6]

For each of ``--seeds``, one job of the cell's timed path (the set-up and
the window of ``run.py``, cut to one job) compared with the reference: the
program's reading. For each of ``--control-seeds``, the reference's
lower-precision control (``refs/<reference>.py``'s ``control``) in the
program's place, compared the same way: it has to come out as not
correct. One process, on the chip the cell asks for; one JSON line per
seed on standard output. The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def control_reading(cell, seed: int) -> dict:
    """The control in the program's place on the graph of ``seed``: the
    numbers ``compare`` gives for each of the jobs a window would run
    first (one per distinct input, at most three)."""
    import jax

    import harness

    graph = cell.module("graphs", cell.config["generator"]).build_graph(
        seed, cell.config)
    jax.block_until_ready(graph)
    live = int(jax.numpy.sum(graph.edge_mask))
    inputs = harness.job_stream(cell.traffic, graph, seed)
    edges = harness.host_edges(graph, live)
    del graph
    ref_mod = cell.module("refs", cell.traffic["reference"])
    limits = cell.traffic["limits"]
    seen, worst = set(), {}
    for i in range(3):
        job = inputs(i)[1]
        key = json.dumps(job, sort_keys=True)
        if key in seen:
            continue
        seen.add(key)
        numbers = ref_mod.compare(ref_mod.control(edges, job),
                                  ref_mod.reference(edges, job))
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, v), v)
    return {
        "correct": all(worst[k] <= limits[k] for k in limits),
        "checks": {k: {"value": worst[k], "limit": limits[k]}
                   for k in limits},
    }


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    from run import start

    cell, devices, peaks = start(args.workload)
    import harness

    for seed in args.seeds:
        line = harness.run(cell, seed, 0.0, False, devices,
                           time.perf_counter(), peaks)
        print(json.dumps({"seed": seed, "side": "program",
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "metrics": line["metrics"],
                          "checks": line["checks"]}), flush=True)
    for seed in args.control_seeds:
        t = time.perf_counter()
        reading = control_reading(cell, seed)
        reading.update(seed=seed, side="control",
                       seconds=time.perf_counter() - t)
        print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
