"""One run of one cell: build, warm up, measure, compare, report.

Everything that belongs to one configuration, traffic mix, placement,
reference or metric is a file of its own, found by the name
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the graph deployment (its file is named in
  ``BENCHMARK.json``), built by ``graphs/<generator>.py``;
* ``traffic/<traffic>.json``: the jobs: the program, the placement, the
  job kind (``fixed``: the same inputs every job; ``rooted``: a root per
  job, drawn from the seed), the reference and the limits of the compared
  numbers;
* ``programs/<program>.palgol``: the Palgol source the jobs run;
* ``placements/<placement>.py``: how a job runs (``prepare``);
* ``refs/<reference>.py``: the plain reference, its control, ``compare``;
* ``metrics/<metric>.py``: ``read(record)`` for each metric, returning
  ``None`` where it finds nothing to read.

A run with ``trace=False`` measures a window of back-to-back jobs and
reports the cell's end-to-end metrics; with ``trace=True`` it profiles one
whole job and reports the per-layer metrics. Either way every job is
compared with the reference once the device state is freed.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import device
import trace_reduce

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]

#: the host span around each job; trace_reduce takes the window from it
JOB_SPAN = "palgol.job"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_module(path: Path):
    """Import ``path`` as a module of its own (file names may hold dots)."""
    name = f"palgol_chip:{path.resolve()}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    program: str
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    def module(self, kind: str, name: str):
        return load_module(self.root / kind / f"{name}.py")


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, spec: dict, checkout: Path = CHECKOUT,
              root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``spec`` (the parsed ``BENCHMARK.json``), with
    its files read from ``root`` and its configuration from ``checkout``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((checkout / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "traffic" / f"{w['traffic']}.json").read_text())
    program = (root / "programs" / f"{traffic['program']}.palgol").read_text()
    return Cell(
        name=name, config=config, traffic=traffic, program=program,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root,
    )


# ---------------------------------------------------------------------------
# traffic: the inputs of each job


def search_keys(graph, seed: int, count: int) -> List[int]:
    """``count`` vertices of degree >= 1 drawn from ``seed``, as Graph500
    draws its search keys (candidates are drawn at random and those of
    degree 0 rejected)."""
    rng = np.random.default_rng(seed % (1 << 64))
    degree = jax.jit(
        lambda dst, v: jnp.searchsorted(dst, v, side="right")
        - jnp.searchsorted(dst, v, side="left")
    )
    keys: List[int] = []
    while len(keys) < count:
        cand = rng.integers(0, graph.n_vertices, size=4 * count)
        deg = np.asarray(degree(graph.dst, jnp.asarray(cand, jnp.int32)))
        keys.extend(int(v) for v in cand[deg > 0])
    return keys[:count]


def job_stream(traffic: dict, graph, seed: int) -> Callable[[int], tuple]:
    """``i -> (program inputs, what the reference needs)`` for job ``i``."""
    kind = traffic["job"]
    if kind == "fixed":
        return lambda i: ({}, {})
    if kind == "rooted":
        keys = search_keys(graph, seed, traffic["keys"])
        field, n = traffic["root_field"], graph.n_vertices

        def job(i):
            root = keys[i % len(keys)]
            mask = np.zeros(n, bool)
            mask[root] = True
            return {field: mask}, {"root": root}

        return job
    raise ValueError(f"unknown job kind {kind!r}")


# ---------------------------------------------------------------------------
# one run


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, peaks: Optional[dict],
        prepare: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result line. ``t_start`` is the
    process's start on ``time.perf_counter``'s clock; ``peaks`` the
    device's entry of ``peaks.json``. ``prepare`` stands in for the
    placement's (tests break the timed path with it)."""
    traffic, config = cell.traffic, cell.config
    with device.CompileClock() as clock:
        t = time.perf_counter()
        graph = cell.module("graphs", config["generator"]).build_graph(
            seed, config)
        jax.block_until_ready(graph)
        live = int(np.asarray(graph.edge_mask).sum())
        log(f"graph: {graph.n_vertices} vertices, {live} live edges in "
            f"{graph.n_edges} slots, built in {time.perf_counter() - t:.3f} s")
        inputs = job_stream(traffic, graph, seed)
        if prepare is None:
            prepare = cell.module("placements", traffic["placement"]).prepare
        job = prepare(cell.program, graph, inputs(0)[0])
        job.warm(inputs(0)[0])
        setup_s = time.perf_counter() - t_start
        compile_s, compiles = clock.seconds, clock.count
        log(f"setup: {setup_s:.3f} s, {compiles} executables in "
            f"{compile_s:.3f} s")

        jobs: List[dict] = []
        summary = None
        t0 = time.perf_counter()
        if trace:
            summary = _traced_job(job, inputs, traffic["result"], jobs)
        else:
            while not jobs or time.perf_counter() - t0 < seconds:
                _one_job(job, inputs, traffic["result"], jobs)
                if "error" in jobs[-1]:
                    break
        window_s = time.perf_counter() - t0
        log(f"window: {len(jobs)} jobs in {window_s:.3f} s, "
            f"{window_s - seconds:.3f} s past --seconds, "
            f"{clock.count - compiles} executables obtained inside the "
            "window")
    memory_peak = device.peak_bytes(devices)

    edges = host_edges(graph, live)
    del job, graph  # the reference runs with the program's state freed
    checks, failed = _compare(cell, edges, jobs)

    record = {
        "setup_s": setup_s, "window_s": window_s, "jobs": jobs,
        "iterations": sum(sum(j.get("trips", [])) for j in jobs),
        "compile_s": compile_s, "trace": summary, "peaks": peaks,
        "n_vertices": edges["n"], "live_edges": live,
        "program": cell.program,
    }
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.module("metrics", m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {
        "correct": bool(jobs) and failed == 0,
        "attempted": len(jobs),
        "failed": failed,
        "metrics": metrics,
        "device": dict(device.describe(devices),
                       memory_peak_bytes=memory_peak),
    }
    if summary is not None:
        line["device"]["busy_s"] = summary["busy_s"]
        line["device"]["window_s"] = summary["window_s"]
        line["breakdown"] = {
            "device_ops": summary["device_ops"],
            "idle_gaps": summary["idle_gaps"],
        }
    line["checks"] = checks  # the compared numbers come last
    return line


def _one_job(job, inputs, result, jobs) -> None:
    program_inputs, ref_inputs = inputs(len(jobs))
    t = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(JOB_SPAN):
            host, trips, counts, itemsize = job.run(program_inputs, result)
    except Exception as e:  # a job that raises is a failed job
        jobs.append({"error": f"{type(e).__name__}: {e}", "ref": ref_inputs})
        log(f"job {len(jobs) - 1}: raised {type(e).__name__}: {e}")
        return
    s = time.perf_counter() - t
    jobs.append({"seconds": s, "trips": trips, "counts": counts,
                 "itemsize": itemsize, "out": host, "ref": ref_inputs})
    log(f"job {len(jobs) - 1}: {s:.3f} s, trips {trips}, {ref_inputs}")


def _traced_job(job, inputs, result, jobs):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tdir:
        with jax.profiler.trace(tdir, profiler_options=options):
            _one_job(job, inputs, result, jobs)
        paths = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        return trace_reduce.reduce(paths[0], JOB_SPAN,
                                   getattr(job, "hlo_text", ""))


def host_edges(graph, live: int) -> dict:
    """The live edges on the host, for the references."""
    return {
        "n": graph.n_vertices,
        "src": np.asarray(graph.src[:live]),
        "dst": np.asarray(graph.dst[:live]),
        "weight": np.asarray(graph.weight[:live]),
    }


def _compare(cell: Cell, edges: dict, jobs: List[dict]):
    """Every job against the reference: ``(worst of each number with its
    limit, jobs failed)``."""
    ref_mod = cell.module("refs", cell.traffic["reference"])
    limits: Dict[str, float] = cell.traffic["limits"]
    worst: Dict[str, float] = {}
    failed = 0
    cache: Dict[str, np.ndarray] = {}
    t = time.perf_counter()
    for j in jobs:
        if "error" in j:
            failed += 1
            continue
        key = json.dumps(j["ref"], sort_keys=True)
        if key not in cache:
            cache[key] = ref_mod.reference(edges, j["ref"])
        numbers = ref_mod.compare(j["out"], cache[key])
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, v), v)
        if any(numbers[k] > limits[k] for k in limits):
            failed += 1
    log(f"reference: {len(cache)} answers in {time.perf_counter() - t:.3f} s")
    checks = {k: {"value": worst.get(k), "limit": limits[k]} for k in limits}
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return checks, failed
