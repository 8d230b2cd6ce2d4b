"""Compile the main-path programs for a described TPU v5e.

Nothing here runs on a chip. Every test compiles ahead of time, from
shapes only, for a v5e topology described by
``jax.experimental.topologies``, so what the TPU compiler refuses (block
tiling, memory, partitioning) shows on a machine without one. The topology
is described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library.
"""

import math
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import algorithms as alg
from repro.core import compile_program
from repro.core import plan as plan_mod
from repro.graph.partition.executor import _make_superstep_fn
from repro.graph.partition.partitioner import HaloSpec, PartitionedGraph
from repro.graph.structure import Graph, from_edge_list

#: Graph500 scale 20, edgefactor 16: directed, and symmetrised (twice that)
SCALE = 20
N = 1 << SCALE
E_DIR = 16 * N
E_SYM = 2 * E_DIR
V5E_HBM_BYTES = 16 * 1024**3

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the cache out of these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("shard",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _graph_shapes(n_edges, sharding, n_vertices=N) -> Graph:
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_

    def arr(dtype):
        return _sds((n_edges,), dtype, sharding)

    # with run ends and the bound of finite weights, as compile_program
    # completes a graph: edge reductions take the segmented scan, and
    # SSSP's filter A[e.id] folds into the value it gathers
    ends = _sds((n_vertices,), i32, sharding)
    return Graph(
        src=arr(i32), dst=arr(i32), weight=arr(f32), edge_mask=arr(b),
        t_src=arr(i32), t_dst=arr(i32), t_weight=arr(f32), t_mask=arr(b),
        n_vertices=n_vertices, n_edges=n_edges, in_ends=ends, out_ends=ends,
        weights_bounded=(True, True),
    )


def _program(name):
    # compile_program needs a concrete graph only for its vertex count
    tiny = from_edge_list(np.array([0]), np.array([1]), N)
    return compile_program(alg.ALL[name], tiny)


@pytest.mark.parametrize(
    "name,n_edges", [("sv", E_SYM), ("wcc", E_SYM), ("sssp", E_DIR)]
)
def test_dense_program_compiles_with_graph_argument(one_chip, name, n_edges):
    cp = _program(name)
    fields = {
        k: _sds(v.shape, v.dtype, one_chip) for k, v in cp.field_struct.items()
    }
    compiled = (
        jax.jit(cp.fn).lower(fields, _graph_shapes(n_edges, one_chip)).compile()
    )
    assert cp.edge_reduce_paths["scan"] >= 1
    assert cp.edge_reduce_paths["scatter"] == 0
    assert cp.edge_reduce_paths["fold"] == (name == "sssp")
    mem = compiled.memory_analysis()
    # the edge arrays arrive as arguments (src, dst, mask at least)
    assert mem.argument_size_in_bytes >= 9 * n_edges
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


#: the on-chip benchmark's Palgol programs
BENCH_PROGRAMS = (
    Path(__file__).resolve().parents[1] / "benchmarks" / "palgol_chip"
    / "programs"
)
#: HLO opcodes that hold or move data and compute nothing
_TRIVIAL = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "broadcast", "copy", "copy-start", "copy-done",
}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][a-z0-9\-]*)\(")


def _instructions(hlo_text):
    """``computation -> [(opcode, called computation, op_name, line)]``."""
    out, name = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            out[name] = []
            continue
        m = _INSTRUCTION.match(line)
        if m and name is not None:
            calls = re.search(r"calls=%?([\w.\-]+)", line)
            op_name = re.search(r'op_name="([^"]*)"', line)
            out[name].append((
                m.group(1), calls and calls.group(1),
                op_name and op_name.group(1), line.strip(),
            ))
    return out


@pytest.mark.parametrize(
    "program,edge_factor", [("sv", 32), ("wcc", 32), ("sssp_rooted", 16)]
)
def test_every_op_of_a_benchmark_program_is_named(
    one_chip, program, edge_factor
):
    """Every instruction of the chip's executable that computes carries a
    ``palgol/`` name (``repro.core.codegen``'s plan-item scopes), so the
    device trace can charge its time to a plan item. Data that XLA only
    moves or broadcasts, and fusions of nothing else, are exempt."""
    n = 1 << 10
    text = (BENCH_PROGRAMS / f"{program}.palgol").read_text()
    inputs = {"Root": np.zeros(n, bool)} if program == "sssp_rooted" else {}
    tiny = from_edge_list(np.array([0]), np.array([1]), n)
    cp = compile_program(text, tiny, initial_fields=inputs)
    fields = {
        k: _sds(v.shape, v.dtype, one_chip) for k, v in cp.field_struct.items()
    }
    graph = _graph_shapes(edge_factor * n, one_chip, n_vertices=n)
    comps = _instructions(
        jax.jit(cp.fn).lower(fields, graph).compile().as_text()
    )
    assert cp.edge_reduce_paths == {
        "scan": cp.edge_reduce_paths["scan"], "scatter": 0,
        "fold": int(program == "sssp_rooted"),
    }

    def trivial(opcode, calls):
        return opcode in _TRIVIAL or (
            opcode == "fusion"
            and all(trivial(o, c) for o, c, _, _ in comps.get(calls, []))
        )

    named = unnamed = 0
    for instrs in comps.values():
        for opcode, calls, op_name, line in instrs:
            if trivial(opcode, calls):
                continue
            if op_name and "palgol" in op_name.split("/"):
                named += 1
            else:
                unnamed += 1
                print("unnamed:", line)
    assert named > 50 and unnamed == 0


def _partitioned_graph_shapes(mesh, n_shards) -> PartitionedGraph:
    split = NamedSharding(mesh, P("shard"))
    whole = NamedSharding(mesh, P())
    # contiguous ranges on an id-ordered Kronecker graph: the hub shard is
    # short, so v_max runs to about half the vertices
    v_max, e_max = N // 2, E_SYM * 13 // (10 * n_shards)
    ghosts, pair = v_max, v_max // 2
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_

    def blk(dtype, *shape):
        return _sds((n_shards,) + shape, dtype, split)

    def halo():
        return HaloSpec(
            ghost_ids=blk(i32, ghosts),
            send_local=blk(i32, n_shards, pair),
            recv_pos=blk(i32, n_shards, pair),
            n_ghost=ghosts, pair_cap=pair,
        )

    return PartitionedGraph(
        starts=_sds((n_shards + 1,), i32, whole), vmask=blk(b, v_max),
        src_g=blk(i32, e_max), src_h=blk(i32, e_max), dst_l=blk(i32, e_max),
        w=blk(f32, e_max), emask=blk(b, e_max),
        t_dst_g=blk(i32, e_max), t_dst_h=blk(i32, e_max),
        t_src_l=blk(i32, e_max), t_w=blk(f32, e_max), t_emask=blk(b, e_max),
        in_ends=blk(i32, v_max), out_ends=blk(i32, v_max),
        halo_in=halo(), halo_out=halo(),
        n_vertices=N, n_edges=E_SYM, n_shards=n_shards, v_max=v_max,
        e_max=e_max,
    )


def test_partitioned_sv_first_superstep_compiles(mesh4):
    cp = _program("sv")
    pg = _partitioned_graph_shapes(mesh4, 4)
    split = NamedSharding(mesh4, P("shard"))
    fields = {
        k: _sds((4, pg.v_max) + v.shape[1:], v.dtype, split)
        for k, v in cp.field_struct.items()
    }
    pp = plan_mod.fuse(plan_mod.lower_program(cp.prog, schedule="pull"))
    first = next(
        it for it in pp.items if isinstance(it, plan_mod.Superstep)
    )
    compiled = _make_superstep_fn(first, pg, mesh4).lower(
        fields, {}, pg
    ).compile()
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


#: the sv-part4-g500-24 cell: Graph500 scale 24 over four chips, at the
#: static sizes ``graphs/kronecker_mesh.py`` rounds to (6 significant
#: bits): edge slots a chip hands the constructor, the routing capacity,
#: ``e_max`` and ``v_max`` as a chip run read them (or above), and ghost
#: counts at their bound (every vertex a shard does not own; a run read
#: 4,587,520 ghosts and a pair capacity of 1,507,328)
PART24 = dict(n=1 << 24, k=66 << 21, cap=66 << 19, e_max=63 << 21,
              v_max=33 << 17, n_ghost=48 << 18, pair_cap=33 << 17)


def _fits(compiled, resident=0):
    """Per-device bytes of one executable (arguments, outputs, temporaries)
    plus ``resident`` bytes held beside it, against a v5e's HBM."""
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes + resident)
    assert used < V5E_HBM_BYTES, used
    return used


@pytest.mark.parametrize("stage", ["sizes", "route", "finish", "halo"])
def test_partition_on_mesh_passes_fit_a_v5e_at_scale_24(mesh4, stage):
    """The constructor's passes at the cell's sizes. The sort between route
    and finish is a plain two-array ``lax.sort`` of 2**27 slots a chip
    (2.7 GB with its operands); it takes about three minutes to compile
    and is left out here."""
    from repro.graph.partition import on_mesh

    p, S = PART24, 4
    split = NamedSharding(mesh4, P("shard"))
    whole = NamedSharding(mesh4, P())
    i32, b = jnp.int32, jnp.bool_
    edges = [_sds((S * p["k"],), dt, split) for dt in (i32, i32, b)]
    bounds = _sds((S + 1,), i32, whole)
    length = on_mesh.sorted_length(max(S * p["cap"], p["e_max"]))
    block = _sds((S * length,), i32, split)  # a sorted block
    # the edge list a chip was handed stays live through every pass; the
    # sizing pass's degrees (8 B a vertex) and the pull ordering's blocks
    # (13 B a slot, 4 B a row of run ends) through the push ordering's
    held = p["k"] * 9 + (0 if stage == "sizes" else
                         p["n"] * 8 + p["e_max"] * 13 + p["v_max"] * 4)
    if stage == "sizes":
        fn, args = on_mesh._sizes_fn(mesh4, p["n"]), edges
    elif stage == "route":
        fn = on_mesh._route_fn(mesh4, p["n"], p["cap"], length, False)
        args = edges + [bounds]
    elif stage == "finish":
        fn = on_mesh._finish_fn(mesh4, p["n"], p["e_max"], p["v_max"], False)
        args = [block, block, bounds, _sds((p["n"],), i32, whole)]
    else:
        fn = on_mesh._halo_fn(mesh4, p["n"], p["v_max"], p["n_ghost"],
                              p["pair_cap"])
        args = [_sds((S, p["e_max"]), i32, split),
                _sds((S, p["e_max"]), b, split),
                _sds((S, p["n"]), b, split), bounds]
    compiled = fn.lower(*args).compile()
    _fits(compiled, resident=held)
    if stage in ("route", "halo"):
        assert "all-to-all" in compiled.as_text()


def test_partitioned_sv_supersteps_fit_a_v5e_at_scale_24(mesh4):
    """Every superstep S-V's fused plan dispatches, on the scale-24 graph
    resident on four chips, beside the graph itself."""
    p, S = PART24, 4
    split = NamedSharding(mesh4, P("shard"))
    whole = NamedSharding(mesh4, P())
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    e_max, v_max, H, Hp = p["e_max"], p["v_max"], p["n_ghost"], p["pair_cap"]

    def blk(dtype, *shape):
        return _sds((S,) + shape, dtype, split)

    def halo():
        return HaloSpec(ghost_ids=blk(i32, H), send_local=blk(i32, S, Hp),
                        recv_pos=blk(i32, S, Hp), n_ghost=H, pair_cap=Hp)

    pg = PartitionedGraph(
        starts=_sds((S + 1,), i32, whole), vmask=blk(b, v_max),
        src_g=blk(i32, e_max), src_h=blk(i32, e_max), dst_l=blk(i32, e_max),
        w=blk(f32, e_max), emask=blk(b, e_max),
        t_dst_g=blk(i32, e_max), t_dst_h=blk(i32, e_max),
        t_src_l=blk(i32, e_max), t_w=blk(f32, e_max), t_emask=blk(b, e_max),
        in_ends=blk(i32, v_max), out_ends=blk(i32, v_max),
        halo_in=halo(), halo_out=halo(), n_vertices=p["n"],
        n_edges=S * e_max, n_shards=S, v_max=v_max, e_max=e_max,
    )
    graph_bytes = sum(
        math.prod(x.shape[1:]) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(pg) if x.shape[0] == S
    )
    text = (BENCH_PROGRAMS / "sv.palgol").read_text()
    tiny = from_edge_list(np.array([0]), np.array([1]), p["n"])
    cp = compile_program(text, tiny)
    pp = plan_mod.fuse(plan_mod.lower_program(cp.prog, schedule="pull"))
    state = [{k: blk(v.dtype, v_max, *v.shape[1:])
              for k, v in cp.field_struct.items()}, {}]
    compiled_steps = []

    def walk(items, loops=()):
        for it in items:
            if not isinstance(it, plan_mod.Superstep):
                walk(it.body, loops + (it.iter_index,))
                continue
            lowered = _make_superstep_fn(it, pg, mesh4, loops).lower(
                *state, pg)
            compiled = lowered.compile()
            # the leaves it reads arrive as arguments; the whole graph is
            # held besides (what it reads is counted twice: a bound)
            _fits(compiled, resident=graph_bytes)
            compiled_steps.append(compiled)
            state[:] = jax.tree_util.tree_map(
                lambda o: _sds(o.shape, o.dtype, split), lowered.out_info)

    walk(pp.items)
    assert len(compiled_steps) >= 2
    assert any("all-to-all" in c.as_text() for c in compiled_steps)
