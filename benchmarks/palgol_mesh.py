import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        "--xla_disable_hlo_passes=while-loop-invariant-code-motion"
    )

"""Palgol programs on the production mesh — the paper-technique §Perf cell.

Lowers the S-V connectivity program (the paper's flagship, Fig. 6) against
the 256-chip mesh with vertex/edge arrays sharded over all axes, under two
chain-access schedules:

  naive — request/reply per hop (hand-written-Pregel wire traffic)
  pull  — the logic-system-derived one-sided schedule (this framework)

and records the roofline terms of one fixed-point iteration each. Writes
experiments/palgol_mesh/<algo>_<mode>.json. Shardings come from
``repro.dist`` (the ``ALL`` logical axis via ``batch_shardings``), the same
rules the live models use.

It also writes ``BENCH_palgol_mesh.json`` at the repo root: per-superstep
communicated bytes of the replicated layout vs the partitioned layout
(``repro.graph.partition``), measured on concrete graphs — the scaling
argument for the halo-exchange subsystem in one artifact.

    PYTHONPATH=src python -m benchmarks.palgol_mesh [--scale 22]
    PYTHONPATH=src python -m benchmarks.palgol_mesh --comm-only

This is a fake-device CPU tool: it lowers against 512 host-platform
devices and never runs on a chip. It sets ``XLA_FLAGS`` on import only
where the caller has not put a device count there already.
"""

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithms as alg
from repro.core import compile_program
from repro.core import ast as past
from repro.dist import sharding as shd
from repro.graph.structure import Graph
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import HW, collective_bytes_from_hlo, roofline_terms


def abstract_graph(n: int, e: int) -> Graph:
    i32 = jnp.int32
    f32 = jnp.float32
    b = jnp.bool_
    sds = jax.ShapeDtypeStruct
    return Graph(
        src=sds((e,), i32), dst=sds((e,), i32), weight=sds((e,), f32),
        edge_mask=sds((e,), b), t_src=sds((e,), i32), t_dst=sds((e,), i32),
        t_weight=sds((e,), f32), t_mask=sds((e,), b),
        n_vertices=n, n_edges=e,
    )


def one_iteration_prog(prog):
    """The iteration body as a standalone program (per-superstep roofline);
    iteration-free programs (e.g. chain4) are used whole."""
    items = prog.progs if isinstance(prog, past.Seq) else (prog,)
    for p in items:
        if isinstance(p, past.Iter):
            return p.body
    return prog


def run_cell(algo: str, mode: str, n: int, e: int, mesh):
    src = alg.ALL[algo]
    # a tiny concrete graph for field discovery; the mesh lowering uses an
    # abstract same-structure graph of production size
    from repro.graph import generators as G

    small = G.erdos_renyi(64, 4.0, directed=False, weighted=True, seed=0)
    init_fields = None
    if algo == "chain4":
        init_fields = {"D": jnp.zeros((64,), jnp.int32)}
    cp = compile_program(src, small, initial_fields=init_fields, schedule=mode)
    body = one_iteration_prog(cp.prog)
    cp_body = dataclasses.replace(
        compile_program(src, small, initial_fields=init_fields, schedule=mode),
        prog=body, n_iters=0,
    )
    ag = abstract_graph(n, e)
    fields = {
        k: jax.ShapeDtypeStruct((n,) + s.shape[1:], s.dtype)
        for k, s in cp.field_struct.items()
    }
    # vertex/edge dims 1-D over the flattened mesh, via the repro.dist rules
    # (ALL logical axis) instead of hand-rolled P(("data","model")) specs
    fshard = shd.batch_shardings("gnn", fields, mesh)
    gshard = shd.batch_shardings("gnn", ag, mesh)

    def step(flds, graph):
        return cp_body.fn(flds, graph=graph)[0]

    with mesh:
        lowered = jax.jit(
            step, in_shardings=(fshard, gshard), out_shardings=fshard
        ).lower(fields, ag)
        compiled = lowered.compile()
    # cost_analysis() is a dict on jax ≥ 0.4.38, a one-element list before
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = cost or {}
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo, mesh.size)
    mem = compiled.memory_analysis()
    # model flops for one S-V iteration ≈ a few ops per edge + per vertex
    model_flops = 4.0 * e + 8.0 * n
    terms = roofline_terms(
        float(cost.get("flops", 0)), float(cost.get("bytes accessed", 0)),
        coll["total"], mesh.size, HW(), model_flops,
    )
    return {
        "algo": algo,
        "mode": mode,
        "n_vertices": n,
        "n_edges": e,
        "collectives": coll,
        "cost": {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float))},
        "memory_peak_gb": (
            mem.argument_size_in_bytes + mem.temp_size_in_bytes
        ) / 1e9,
        "roofline": terms,
    }


def comm_comparison(n_shards: int = 8) -> dict:
    """Replicated-vs-partitioned bytes per superstep on concrete graphs.

    Graphs are chosen to span locality regimes: a range-local grid (the
    partitioned layout's best case — halo ≪ N), and an R-MAT power-law
    graph (its worst case — cuts everywhere). Runs host-side (the
    partitioner needs no devices), so it is cheap enough for CI and for
    the partition acceptance test.
    """
    from repro.graph import generators as G
    from repro.graph.partition import comm_bytes_report

    cells = {}
    graphs = {
        "grid_512x8": G.grid2d(512, 8),
        "rmat_s12": G.rmat(12, avg_degree=8.0, directed=True, seed=5),
    }
    for gname, g in graphs.items():
        rep = comm_bytes_report(g, n_shards)
        cells[gname] = rep
    return {
        "n_shards": n_shards,
        "per_graph": cells,
        "note": (
            "bytes per pull superstep for one f32 vertex field, aggregate "
            "across devices; 'padded' is the static-shape all_to_all cost "
            "the implementation actually pays"
        ),
    }


#: schedule → STM cost-model key for the UNFUSED expansion (what
#: ``run_bsp(..., fuse=False)`` executes)
SCHED_KEYS = {
    "pull": "pull_staged",
    "push": "push",
    "naive": "naive",
    "auto": "auto",
}

#: schedule → STM cost-model key for the §4.3-FUSED plan (state merging +
#: iteration fusion — what every executor dispatches by default)
FUSED_KEYS = {
    "pull": "fused_pull",
    "push": "fused_push",
    "naive": "fused_naive",
    "auto": "fused_auto",
}


def schedule_report(
    algos=("sssp", "wcc", "sv", "chain4", "pagerank"),
    n_shards: int = 8,
    grid_shape=(512, 8),
) -> dict:
    """Per-schedule superstep counts and modeled bytes, derived from the
    plan IR (``repro.core.plan``) — the (executor × schedule) cost surface
    in one artifact.

    For each algorithm and each schedule (pull / push / naive / auto) we
    lower every step to its StepPlan, execute once on a small graph to get
    real trip counts, and report: the per-step op lists with their
    byte-model estimates, total executed supersteps (the STM cost model
    evaluated on the measured trips — equal to what every executor
    actually charges), and the partitioned layout's padded bytes ×
    supersteps per iteration on the grid graph.

    Each schedule cell reports both the unfused (``fuse=False``) and the
    §4.3-fused (default execution) superstep totals — the
    ``bench-plan-regression`` gate diffs both, so neither the per-step
    expansion nor the program-level fuse pass can drift silently. Each
    algo cell also records the measured per-iteration fixed-point frontier
    (``active_set_per_iter``, from a staged ``run_bsp`` — the live
    request-set figure ``ByteCostModel.request_set`` models) and, for the
    chain-access programs, the measured request-dedup savings of
    ``gather_global``'s unique pass (``gather_dedup``).

    ``auto_byte_regimes`` shows where the byte-aware selector flips: under
    the *dense* regime (every vertex reads its chain — pull's best case)
    and the *sparse* regime (request set = the grid halo, combined further
    by message dedup — deep chains with tiny frontiers), per step. The
    regime cost models always derive from the canonical 512×8 grid (its
    host-side partition costs milliseconds), so the selections the
    ``bench-plan-regression`` gate diffs are identical between ``--quick``
    runs and the committed full-size report; ``grid_shape`` only scales
    the padded-byte figures, which the gate deliberately ignores.
    """
    from repro.core.plan import program_plan_records
    from repro.graph import generators as G
    from repro.graph.partition import (
        byte_cost_model,
        comm_bytes_report,
        request_dedup_report,
    )
    from repro.pregel import run_bsp

    grid = G.grid2d(*grid_shape)
    grid_rep = comm_bytes_report(grid, n_shards)
    grid_bytes = grid_rep["partitioned_padded_bytes_per_superstep"]
    small = G.erdos_renyi(64, 4.0, directed=False, weighted=True, seed=0)
    # the two byte regimes the selector is judged under — pinned to the
    # canonical grid so they are graph-size-invariant across --quick
    regime_grid = G.grid2d(512, 8)
    halo_total = comm_bytes_report(regime_grid, n_shards)["partition"][
        "halo_total"
    ]
    dense_costs = byte_cost_model(regime_grid, n_shards)
    sparse_costs = byte_cost_model(
        regime_grid,
        n_shards,
        request_set=max(1, halo_total),
        combined_request_set=max(1, halo_total // 4),
    )
    out = {}
    for algo in algos:
        init_fields = None
        if algo == "chain4":
            # a random indirection field: makes the chain request sets (and
            # the dedup measurement below) non-degenerate; plan-derived
            # counts are structural, so the regression gate is unaffected
            rng = np.random.default_rng(0)
            init_fields = {"D": jnp.asarray(rng.integers(0, 64, 64), jnp.int32)}
        cp = compile_program(alg.ALL[algo], small, initial_fields=init_fields)
        dense_out, trips, counts = cp.run(init_fields)
        staged = run_bsp(
            cp.prog, small, cp.init_fields(init_fields), schedule="pull"
        )

        cell = {
            # measured fixed-point frontier per loop entry, per iteration —
            # the live request-set instrumentation replacing the supplied
            # ByteCostModel.request_set constant
            "active_set_per_iter": staged.active_sets,
        }
        # measured request-dedup savings of gather_global's unique pass on
        # the programs' real indirection fields (the chain request sets)
        if algo == "sv":
            cell["gather_dedup"] = request_dedup_report(
                dense_out["D"], small.n_vertices
            )
        elif algo == "chain4":
            cell["gather_dedup"] = request_dedup_report(
                init_fields["D"], small.n_vertices
            )
        for sched, key in SCHED_KEYS.items():
            total = counts[key]
            fused_total = counts[FUSED_KEYS[sched]]
            cell[sched] = {
                "steps": program_plan_records(
                    cp.step_plans(sched), costs=dense_costs
                ),
                "executed_supersteps": total,
                "fused_supersteps": fused_total,
                "grid_padded_bytes_total": total * grid_bytes,
                "grid_padded_bytes_total_fused": fused_total * grid_bytes,
            }
        cell["auto_byte_regimes"] = {
            regime: [
                r["resolved"]
                for r in program_plan_records(
                    dataclasses.replace(cp, byte_costs=costs).step_plans(
                        "auto"
                    ),
                    costs=costs,
                )
            ]
            for regime, costs in (
                ("dense", dense_costs), ("sparse", sparse_costs),
            )
        }
        out[algo] = cell
    return {
        "n_shards": n_shards,
        "grid_padded_bytes_per_superstep": grid_bytes,
        "sparse_regime": {
            "request_set": max(1, halo_total),
            "combined_request_set": max(1, halo_total // 4),
        },
        "per_algo": out,
        "note": (
            "superstep counts are plan-derived (STM cost models on "
            "measured trips): 'executed_supersteps' is the unfused per-op "
            "expansion (fuse=False), 'fused_supersteps' the §4.3-fused "
            "plan every executor dispatches by default; per-step 'bytes' "
            "is the plan byte model under the dense regime; bytes totals "
            "are the grid graph's partitioned padded per-superstep cost "
            "times supersteps"
        ),
    }


def check_plan_regression(bench: dict, committed_path: Path) -> list:
    """Diff plan-derived superstep counts per (program × schedule) —
    unfused AND fused — against the committed benchmark JSON. Returns a
    list of drift descriptions (empty = clean). Byte figures and the
    measured frontier/dedup cells are deliberately NOT compared — they
    scale with the grid, which ``--quick`` shrinks; the plan-derived
    counts and resolved schedules must be graph-size-invariant.
    """
    committed = json.loads(committed_path.read_text())
    drifts = []
    old_algos = committed.get("schedules", {}).get("per_algo", {})
    new_algos = bench["schedules"]["per_algo"]
    for algo in sorted(set(old_algos) | set(new_algos)):
        if algo not in old_algos or algo not in new_algos:
            drifts.append(f"{algo}: present in only one report")
            continue
        for sched in SCHED_KEYS:
            old, new = old_algos[algo].get(sched), new_algos[algo].get(sched)
            if old is None or new is None:
                drifts.append(f"{algo}/{sched}: present in only one report")
                continue
            for fld in ("executed_supersteps", "fused_supersteps"):
                if old.get(fld) != new.get(fld):
                    drifts.append(
                        f"{algo}/{sched}: {fld} {old.get(fld)} -> "
                        f"{new.get(fld)}"
                    )
            old_steps = [
                (s["resolved"], s["supersteps"]) for s in old["steps"]
            ]
            new_steps = [
                (s["resolved"], s["supersteps"]) for s in new["steps"]
            ]
            if old_steps != new_steps:
                drifts.append(
                    f"{algo}/{sched}: per-step plans {old_steps} -> {new_steps}"
                )
        for regime in ("dense", "sparse"):
            old = old_algos[algo].get("auto_byte_regimes", {}).get(regime)
            new = new_algos[algo].get("auto_byte_regimes", {}).get(regime)
            if old != new:
                drifts.append(
                    f"{algo}/auto[{regime}]: resolved {old} -> {new}"
                )
    return drifts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=26,
                    help="log2 vertices (default 64M vertices, 1B edges)")
    ap.add_argument("--algos", default="sv,wcc")
    ap.add_argument("--comm-only", action="store_true",
                    help="only write BENCH_palgol_mesh.json (no 512-dev "
                         "roofline lowering)")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: tiny grid, comm+schedule report only — "
                         "plan-derived counts are identical to the full run")
    ap.add_argument("--out", default=None,
                    help="where to write the benchmark JSON (default: "
                         "repo-root BENCH_palgol_mesh.json)")
    ap.add_argument("--check", default=None, metavar="COMMITTED_JSON",
                    help="diff plan-derived superstep counts per (program "
                         "× schedule) against a committed report; exit 2 "
                         "on drift (the bench-plan-regression CI gate)")
    ap.add_argument("--shards", type=int, default=8)
    args = ap.parse_args()

    grid_shape = (64, 8) if args.quick else (512, 8)
    bench = comm_comparison(args.shards)
    bench["schedules"] = schedule_report(
        n_shards=args.shards, grid_shape=grid_shape
    )
    repo_root = Path(__file__).resolve().parent.parent
    out_path = (
        Path(args.out) if args.out else repo_root / "BENCH_palgol_mesh.json"
    )
    out_path.write_text(json.dumps(bench, indent=1))
    for algo, cell in bench["schedules"]["per_algo"].items():
        per = {
            s: f"{cell[s]['fused_supersteps']}/{cell[s]['executed_supersteps']}"
            for s in SCHED_KEYS
            if s in cell
        }
        print(f"{algo}: supersteps fused/unfused {per} "
              f"auto_bytes={cell['auto_byte_regimes']}", flush=True)
        if "gather_dedup" in cell:
            d = cell["gather_dedup"]
            print(
                f"  gather dedup: {d['raw_request_slots']} -> "
                f"{d['deduped_request_slots']} slots "
                f"({d['raw_bytes']} -> {d['deduped_bytes']} B)",
                flush=True,
            )
    if args.check:
        drifts = check_plan_regression(bench, Path(args.check))
        if drifts:
            print("PLAN REGRESSION: plan-derived counts drifted from "
                  f"{args.check}:", flush=True)
            for d in drifts:
                print(f"  {d}", flush=True)
            sys.exit(2)
        print(f"plan-regression check vs {args.check}: clean", flush=True)
    for gname, rec in bench["per_graph"].items():
        red = rec["reduction_vs_replicated"]
        nph = rec["vertices_per_halo_entry"]
        print(
            f"{gname}: replicated={rec['replicated_bytes_per_superstep']/1e3:.1f}KB "
            f"partitioned(padded)={rec['partitioned_padded_bytes_per_superstep']/1e3:.1f}KB "
            f"reduction={'inf' if red is None else f'{red:.1f}'}x "
            f"N/halo={'inf' if nph is None else f'{nph:.1f}'}",
            flush=True,
        )
    if args.comm_only or args.quick:
        return

    n = 1 << args.scale
    e = n * 16
    mesh = make_production_mesh()
    out_dir = Path("experiments/palgol_mesh")
    out_dir.mkdir(parents=True, exist_ok=True)
    for algo in args.algos.split(","):
        for mode in ("naive", "pull"):
            rec = run_cell(algo, mode, n, e, mesh)
            p = out_dir / f"{algo}_{mode}.json"
            p.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(
                f"{algo}/{mode}: collective={r['collective_s']*1e3:.2f}ms "
                f"compute={r['compute_s']*1e3:.3f}ms "
                f"memory={r['memory_s']*1e3:.2f}ms "
                f"coll_bytes/dev={rec['collectives']['total']/1e6:.1f}MB "
                f"bottleneck={r['bottleneck']}",
                flush=True,
            )


if __name__ == "__main__":
    main()
