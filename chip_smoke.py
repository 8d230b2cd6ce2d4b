#!/usr/bin/env python3
"""Chip smoke test: Palgol jobs on a Graph500 scale-22 graph on a TPU.

    python3 chip_smoke.py [--seed N]             # one chip
    python3 chip_smoke.py --chips 4 [--seed N]   # partitioned, four chips

Builds a Graph500 Kronecker graph from ``--seed`` (scale 22, edgefactor
16, A=0.57, B=0.19, C=0.19): symmetrised for S-V and WCC, directed and
weighted for SSSP. Every job runs through the entry point a user calls,
and every result is compared with an independent host reference from
``scipy.sparse.csgraph``: ``connected_components`` (both programs label a
component with its lowest vertex id, so the match is exact) and
``dijkstra`` from vertex 0 (``allclose`` at rtol 1e-5, inf where
unreachable).

One chip:
  * the fused dense compiler, ``compile_program(...).run()``: S-V, WCC, SSSP;
  * the staged executor, ``run_bsp`` (pull schedule, fused plan): S-V, SSSP.

``--chips 4``, and nothing else:
  * ``run_bsp(placement="partitioned")`` on a 4-shard mesh: S-V, SSSP,
    after checking that the mesh holds four distinct TPU devices and that
    every per-shard array is split across them, not replicated.

The lines before the last are bring-up context (build, compile and run
seconds, supersteps, peak device bytes), not benchmark metrics. A run
that passes ends with one JSON line ``{"ok": true, "device": {...}}``.
Any mismatch or error exits non-zero without that line, and so does a
machine where JAX finds no TPU, before any work. Everything runs in this
one process.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

SCALE = 22
EDGEFACTOR = 16
ROOT = Path(__file__).resolve().parent


def tpu_devices(count: int):
    """The first ``count`` TPU devices; exits non-zero where there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r})"
        )
    if len(devices) < count:
        raise SystemExit(
            f"chip_smoke: {count} chips requested, {len(devices)} found"
        )
    return devices[:count]


def log(phase: str, **kv) -> None:
    parts = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {parts}", flush=True)


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device (None where the backend has none)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


class CompileClock:
    """Sums the backend-compile seconds JAX reports through
    ``jax.monitoring`` while it is registered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
        return False


# ---------------------------------------------------------------------------
# graphs and host references


def build_graphs(scale: int, seed: int):
    """(symmetrised graph, directed weighted graph, host seconds)."""
    from repro.graph import generators as G

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=2) as pool:
        fu = pool.submit(
            G.rmat, scale, EDGEFACTOR, directed=False, seed=seed
        )
        fd = pool.submit(
            G.rmat, scale, EDGEFACTOR, directed=True, weighted=True,
            seed=seed + 1,
        )
        gu, gd = fu.result(), fd.result()
    return gu, gd, time.perf_counter() - t0


def _host_edges(graph):
    import numpy as np

    m = np.asarray(graph.edge_mask)
    return (
        np.asarray(graph.src)[m].astype(np.int64),
        np.asarray(graph.dst)[m].astype(np.int64),
        np.asarray(graph.weight)[m],
    )


def references(gu, gd) -> dict:
    """scipy answers: lowest vertex id of each vertex's component in ``gu``
    and shortest distances from vertex 0 along ``gd``'s src→dst edges."""
    import numpy as np
    import scipy.sparse as sp
    from scipy.sparse import csgraph

    t0 = time.perf_counter()
    n = gu.n_vertices
    src, dst, _ = _host_edges(gu)
    adj = sp.csr_matrix(
        (np.ones(src.size, np.int8), (src, dst)), shape=(n, n)
    )
    _, labels = csgraph.connected_components(adj, directed=False)
    # vertices scan in id order, so each label's first index is its lowest id
    _, first = np.unique(labels, return_index=True)
    components = first[labels].astype(np.int32)
    del adj, src, dst

    n = gd.n_vertices
    src, dst, w = _host_edges(gd)
    # csr_matrix sums duplicate entries: keep each edge's lightest copy
    key = src * n + dst
    order = np.argsort(key)
    key, w = key[order], w[order]
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    wmin = np.minimum.reduceat(w, starts).astype(np.float64)
    ukey = key[starts]
    adj = sp.csr_matrix((wmin, (ukey // n, ukey % n)), shape=(n, n))
    dist = csgraph.dijkstra(adj, directed=True, indices=0)
    return {
        "components": components,
        "dist": dist,
        "seconds": time.perf_counter() - t0,
    }


def check_components(name: str, got, ref) -> None:
    import numpy as np

    got = np.asarray(got)
    bad = int(np.count_nonzero(got != ref))
    if got.shape != ref.shape or bad:
        raise RuntimeError(
            f"{name}: {bad} of {ref.size} component labels differ from "
            "scipy connected_components"
        )


def check_dist(name: str, got, ref) -> None:
    import numpy as np

    got = np.asarray(got, np.float64)
    if got.shape != ref.shape or not np.array_equal(
        np.isinf(got), np.isinf(ref)
    ):
        raise RuntimeError(f"{name}: reachable set differs from scipy dijkstra")
    fin = np.isfinite(ref)
    if not np.allclose(got[fin], ref[fin], rtol=1e-5, atol=0.0):
        worst = float(np.max(np.abs(got[fin] - ref[fin]) / ref[fin].clip(1e-30)))
        raise RuntimeError(
            f"{name}: distances differ from scipy dijkstra (rel err {worst})"
        )


def _jobs(gu, gd, refs):
    """(name, source, graph, result field, check) for S-V, WCC, SSSP."""
    from repro.core import algorithms as alg

    def comp(name, got):
        check_components(name, got, refs()["components"])

    def dist(name, got):
        check_dist(name, got, refs()["dist"])

    return {
        "sv": (alg.SV, gu, "D", comp),
        "wcc": (alg.WCC, gu, "C", comp),
        "sssp": (alg.SSSP, gd, "D", dist),
    }


# ---------------------------------------------------------------------------
# phases


def one_chip(scale: int, seed: int, devices) -> None:
    """Fused dense ``cp.run()`` (S-V, WCC, SSSP) and staged ``run_bsp``
    (S-V, SSSP) on JAX's default device, each checked against scipy;
    ``devices`` are the ones whose peak bytes each phase reports."""
    import jax

    from repro.core import compile_program
    from repro.pregel import run_bsp

    gu, gd, build_s = build_graphs(scale, seed)
    log(
        "graph", scale=scale, edgefactor=EDGEFACTOR, vertices=gu.n_vertices,
        sym_edges=gu.n_edges, directed_edges=gd.n_edges,
        host_build_s=round(build_s, 3),
    )
    with ThreadPoolExecutor(max_workers=1) as pool:
        ref_future = pool.submit(references, gu, gd)
        jobs = _jobs(gu, gd, ref_future.result)
        for name in ("sv", "wcc", "sssp"):
            src, graph, field, check = jobs[name]
            cp = compile_program(src, graph)
            with CompileClock() as clock:
                t0 = time.perf_counter()
                out, trips, counts = cp.run()
                jax.block_until_ready(out)
                first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            out, trips, counts = cp.run()
            jax.block_until_ready(out)
            run_s = time.perf_counter() - t0
            check(f"dense/{name}", out[field])
            log(
                f"dense/{name}", match="scipy", compile_s=round(clock.seconds, 3),
                first_call_s=round(first_s, 3), run_s=round(run_s, 3),
                trips=trips, supersteps=counts["fused_pull"],
                peak_bytes=peak_bytes(devices),
            )
        for name in ("sv", "sssp"):
            src, graph, field, check = jobs[name]
            cp = compile_program(src, graph)
            fields = cp.init_fields()
            with CompileClock() as clock:
                t0 = time.perf_counter()
                res = run_bsp(cp.prog, graph, fields, schedule="pull")
                jax.block_until_ready(res.fields)
                wall_s = time.perf_counter() - t0
            check(f"staged/{name}", res.fields[field])
            log(
                f"staged/{name}", match="scipy",
                compile_s=round(clock.seconds, 3),
                wall_s=round(wall_s, 3), supersteps=res.supersteps,
                trips=res.trips, peak_bytes=peak_bytes(devices),
            )
        log("reference", host_s=round(ref_future.result()["seconds"], 3))


def _check_split(tree, specs, mesh, what: str) -> None:
    """Every leaf whose spec names the shard axis is split over all of
    ``mesh``'s devices (one block each), not replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    n = mesh.devices.size
    leaves = jax.tree_util.tree_leaves(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    if len(leaves) != len(spec_leaves):
        raise RuntimeError(f"{what}: spec tree does not match the arrays")
    split = 0
    for leaf, spec in zip(leaves, spec_leaves):
        if len(spec) == 0 or spec[0] is None:
            continue
        shard_shape = leaf.sharding.shard_shape(leaf.shape)
        if (
            leaf.sharding.is_fully_replicated
            or len(leaf.sharding.device_set) != n
            or shard_shape[0] * n != leaf.shape[0]
        ):
            raise RuntimeError(
                f"{what}: a [{leaf.shape[0]}, ...] array is not split over "
                f"{n} devices (sharding {leaf.sharding})"
            )
        split += 1
    if split == 0:
        raise RuntimeError(f"{what}: no array is sharded")


def four_chips(scale: int, seed: int, devices) -> None:
    """``run_bsp(placement="partitioned")`` for S-V and SSSP over a 4-shard
    mesh of ``devices``, each checked against scipy."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import compile_program
    from repro.dist import sharding as shd
    from repro.graph.partition import partition_fields, partition_graph
    from repro.graph.partition.executor import pg_partition_specs
    from repro.pregel import run_bsp

    mesh = shd.shard_mesh(devices=devices)
    if len(set(mesh.devices.flat)) != len(devices):
        raise RuntimeError("mesh devices are not distinct")
    gu, gd, build_s = build_graphs(scale, seed)
    log(
        "graph", scale=scale, edgefactor=EDGEFACTOR, vertices=gu.n_vertices,
        sym_edges=gu.n_edges, directed_edges=gd.n_edges,
        host_build_s=round(build_s, 3),
    )
    with ThreadPoolExecutor(max_workers=1) as pool:
        ref_future = pool.submit(references, gu, gd)
        jobs = _jobs(gu, gd, ref_future.result)
        for name in ("sv", "sssp"):
            src, graph, field, check = jobs[name]
            t0 = time.perf_counter()
            pg = partition_graph(graph, len(devices))
            pg = jax.device_put(pg, shd.vertex_partition_shardings(pg, mesh))
            jax.block_until_ready(pg)
            part_s = time.perf_counter() - t0
            _check_split(pg, pg_partition_specs(pg), mesh, f"{name} graph")
            cp = compile_program(src, graph)
            fields = cp.init_fields()
            pfields = partition_fields(pg, fields)
            pfields = jax.device_put(
                pfields, shd.vertex_partition_shardings(pfields, mesh)
            )
            _check_split(
                pfields, {k: P(shd.SHARD) for k in pfields}, mesh,
                f"{name} fields",
            )
            with CompileClock() as clock:
                t0 = time.perf_counter()
                res = run_bsp(
                    cp.prog, pg, fields, schedule="pull",
                    placement="partitioned", mesh=mesh,
                )
                jax.block_until_ready(res.fields)
                wall_s = time.perf_counter() - t0
            check(f"partitioned/{name}", res.fields[field])
            log(
                f"partitioned/{name}", match="scipy", shards=len(devices),
                v_max=pg.v_max, e_max=pg.e_max, partition_s=round(part_s, 3),
                compile_s=round(clock.seconds, 3), wall_s=round(wall_s, 3),
                supersteps=res.supersteps, trips=res.trips,
                peak_bytes=peak_bytes(devices),
            )
        log("reference", host_s=round(ref_future.result()["seconds"], 3))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1703)
    args = ap.parse_args(argv)

    devices = tpu_devices(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    d = devices[0]
    log(
        "device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devices), seed=args.seed, compile_cache=cache_dir,
    )
    if args.chips == 4:
        four_chips(SCALE, args.seed, devices)
    else:
        one_chip(SCALE, args.seed, devices)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
