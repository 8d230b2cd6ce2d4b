"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build abstract parameters (ShapeDtypeStructs — zero host
memory), jit the real step function (train step WITH optimizer update, or
prefill/decode/serve), lower against the production mesh, compile, and
record ``memory_analysis()`` (proves it fits), ``cost_analysis()`` (flops /
bytes for §Roofline) and the collective-bytes breakdown parsed from the
partitioned HLO.

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape train_4k --mesh single

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json and are
summarized into EXPERIMENTS.md by benchmarks/roofline_report.py.

This is a fake-device CPU tool: it compiles for 256/512 host-platform
devices and never runs on a chip. It sets ``XLA_FLAGS`` on import only
where the caller has not put a device count there already.
"""

import os
# NOTE: while-loop LICM is disabled because XLA:CPU shadows every bf16 dot
# operand with an f32 convert; LICM hoists those converts out of the scan
# loops, materializing f32 copies of whole [L,B,S,D] remat stacks. TPU has
# native bf16 MXU input, so the hoisted copies don't exist there — disabling
# the pass makes the CPU memory analysis TPU-faithful.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        "--xla_disable_hlo_passes=while-loop-invariant-code-motion"
    )

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.gnn import models as gm
from repro.models.recsys import autoint
from repro.models.transformer import model as tm
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.roofline.analysis import (
    HW,
    collective_bytes_from_hlo,
    roofline_terms,
)

OUT_DIR = Path("experiments/dryrun")


# ---------------------------------------------------------------------------
# per-family step functions + input specs


def _lm_probe_cfg(cfg):
    """2-layer fully-unrolled variant: XLA cost analysis counts while-loop
    bodies once, so f(probe2) − f(scan) isolates one true layer's cost."""
    return dataclasses.replace(cfg, n_layers=2, scan_unroll=2)


# gradient-accumulation microbatches per (arch, shape): the global batch is
# unchanged (identical optimizer semantics); activation memory scales 1/M.
# Unrolled python loop, so cost_analysis counts every microbatch.
MICROBATCH = {
    ("qwen3-moe-235b-a22b", "train_4k"): 8,
    ("qwen3-32b", "train_4k"): 2,
    ("qwen2.5-32b", "train_4k"): 2,
    ("deepseek-moe-16b", "train_4k"): 2,
}


# "fsdp" (2D params) vs "zero1" (model-sharded params, 2D optimizer state).
# Hillclimb result (EXPERIMENTS §Perf): zero1 removes the per-layer weight
# all-gathers (428→30 GB/dev on qwen3-32b train) and still fits; dense-LM
# train cells default to it. MoE archs must stay fsdp — expert stacks are
# 29 GB/device without the data-axis shard.
PARAM_MODE = {
    ("qwen3-32b", "train_4k"): "zero1",
    ("qwen2.5-32b", "train_4k"): "zero1",
    ("h2o-danube-1.8b", "train_4k"): "zero1",
}


def lm_cell(spec, shape_id, shape, mesh, cfg=None):
    cfg = cfg or spec.config
    kind = shape["kind"]
    seq, batch = shape["seq_len"], shape["global_batch"]
    params = tm.abstract_params(cfg)
    mode = PARAM_MODE.get((spec.arch_id, shape_id), "fsdp")
    pshard = shd.param_shardings("lm", params, mesh, mode=mode)
    oc = AdamWConfig(
        state_dtype="bfloat16" if cfg.n_params() > 1e11 else None
    )
    if kind == "train":
        opt = jax.eval_shape(lambda p: adamw_init(p, oc), params)
        # optimizer state always 2D-sharded (ZeRO-1 keeps it sharded even
        # when the stored params are only model-sharded)
        opt_shard_leaf = shd.param_shardings("lm", params, mesh, mode="fsdp")
        oshard = {
            "m": opt_shard_leaf,
            "v": opt_shard_leaf,
            "step": shd.replicated(jnp.zeros(()), mesh),
        }
        batch_specs = tm.input_specs(cfg, "train", seq, batch)
        bshard = shd.batch_shardings("lm", batch_specs, mesh)
        micro = MICROBATCH.get((spec.arch_id, shape_id), 1)

        def step(p, o, b):
            if micro == 1:
                loss, g = jax.value_and_grad(
                    lambda q: tm.loss_fn(q, b, cfg)
                )(p)
            else:
                # gradient accumulation via lax.scan: one microbatch's
                # buffers alive at a time (an unrolled loop lets XLA:CPU
                # keep every microbatch's temporaries simultaneously —
                # refuted hypothesis H6 in EXPERIMENTS.md §Perf)
                mb = batch // micro
                stacked = {
                    k: v.reshape((micro, mb) + v.shape[1:])
                    for k, v in b.items()
                }

                def mb_body(carry, sub):
                    loss_acc, g_acc = carry
                    li, gi = jax.value_and_grad(
                        lambda q: tm.loss_fn(q, sub, cfg)
                    )(p)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, c: a + c / micro, g_acc, gi
                    )
                    return (loss_acc + li / micro, g_acc), None

                g0 = jax.tree_util.tree_map(
                    lambda q: jnp.zeros(q.shape, jnp.bfloat16
                                        if q.dtype == jnp.bfloat16
                                        else jnp.float32),
                    p,
                )
                (loss, g), _ = jax.lax.scan(
                    mb_body, (jnp.zeros((), jnp.float32), g0), stacked
                )
            p, o = adamw_update(g, o, p, oc)
            return p, o, loss

        fn = jax.jit(
            step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        args = (params, opt, batch_specs)
        tokens = batch * seq
        model_flops = 6.0 * cfg.n_active_params() * tokens
    elif kind == "prefill":
        batch_specs = tm.input_specs(cfg, "prefill", seq, batch)
        bshard = shd.batch_shardings("lm", batch_specs, mesh)
        cache_c = tm.cache_len(cfg, seq)
        cache_spec = shd.lm_cache_spec(mesh, cfg, batch, cache_c)
        from jax.sharding import NamedSharding, PartitionSpec as P

        out_shard = (
            NamedSharding(mesh, shd.lm_batch_spec(mesh, batch)),
            {
                "k": NamedSharding(mesh, cache_spec),
                "v": NamedSharding(mesh, cache_spec),
                "length": NamedSharding(mesh, P()),
            },
        )

        def step(p, b):
            # production prefill: last-position logits only (sampling needs
            # no more; full [B,S,V] logits would be ~20 GB/device at 32k)
            return tm.prefill(p, b["tokens"], cfg, full_logits=False)

        fn = jax.jit(step, in_shardings=(pshard, bshard),
                     out_shardings=out_shard)
        args = (params, batch_specs)
        model_flops = 2.0 * cfg.n_active_params() * batch * seq
    elif kind == "decode":
        specs = tm.input_specs(cfg, "decode", seq, batch)
        cache_c = tm.cache_len(cfg, seq)
        from jax.sharding import NamedSharding, PartitionSpec as P

        cache_spec = shd.lm_cache_spec(mesh, cfg, batch, cache_c)
        cshard = {
            "k": NamedSharding(mesh, cache_spec),
            "v": NamedSharding(mesh, cache_spec),
            "length": NamedSharding(mesh, P()),
        }
        tshard = NamedSharding(
            mesh, shd.lm_batch_spec(mesh, batch)
        )

        def step(p, cache, toks):
            return tm.decode_step(p, cache, toks, cfg)

        fn = jax.jit(
            step,
            in_shardings=(pshard, cshard, tshard),
            out_shardings=(tshard, cshard),
            donate_argnums=(1,),
        )
        args = (params, specs["cache"], specs["tokens"])
        # per-token weight read + KV attention flops
        kv_flops = (
            2.0 * batch * cfg.n_layers * cfg.n_heads * cache_c
            * cfg.head_dim * 2
        )
        model_flops = 2.0 * cfg.n_active_params() * batch + kv_flops
    else:
        raise ValueError(kind)
    return fn, args, model_flops


def _pad1024(n: int) -> int:
    """Graph arrays are padded so node/edge counts divide the mesh axes —
    otherwise batch-sharding constraints silently drop (masked rows are the
    standard padding mechanism of the substrate)."""
    return -(-n // 1024) * 1024


def gnn_cell(spec, shape_id, shape, mesh):
    cfg = configs.resolve_gnn_config(spec.config, shape_id, shape)
    kind = shape["kind"]
    if kind == "full_graph":
        shape = dict(
            shape,
            n_nodes=_pad1024(shape["n_nodes"]),
            n_edges=_pad1024(shape["n_edges"]),
        )
    oc = AdamWConfig()
    if kind == "minibatch":
        # generic sampled-subgraph: seeds + 2 sampled hops as a block graph
        b = shape["batch_nodes"]
        f0, f1 = shape["fanouts"]
        n_sub = b * (1 + f0 + f0 * f1)
        e_sub = b * (f0 + f0 * f1)
        batch_specs = gm.input_specs(
            cfg, "full_graph", n_nodes=n_sub, n_edges=e_sub,
            d_feat=shape["d_feat"],
        )
    elif kind == "batched_graphs":
        batch_specs = gm.input_specs(
            cfg, "batched_graphs", batch=shape["batch"],
            n_nodes=shape["n_nodes"], n_edges=shape["n_edges"],
            d_feat=shape["d_feat"],
        )
    else:
        batch_specs = gm.input_specs(
            cfg, "full_graph", n_nodes=shape["n_nodes"],
            n_edges=shape["n_edges"], d_feat=shape["d_feat"],
        )
    params = gm.abstract_params(cfg)
    pshard = shd.param_shardings("gnn", params, mesh)
    opt = jax.eval_shape(lambda p: adamw_init(p, oc), params)
    oshard = {"m": pshard, "v": pshard,
              "step": shd.replicated(jnp.zeros(()), mesh)}
    bshard = shd.batch_shardings("gnn", batch_specs, mesh)

    def step(p, o, b):
        loss, g = jax.value_and_grad(lambda q: gm.loss_fn(q, b, cfg))(p)
        p, o = adamw_update(g, o, p, oc)
        return p, o, loss

    fn = jax.jit(
        step,
        in_shardings=(pshard, oshard, bshard),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1),
    )
    args = (params, opt, batch_specs)
    # analytic model flops: 3 matmul passes (fwd + 2 bwd) over layer matmuls
    n_nodes = batch_specs["x"].shape[0]
    n_edges = batch_specs["src"].shape[0]
    d = cfg.d_hidden
    d_in = cfg.d_in
    per_layer = 2 * n_nodes * (d_in if cfg.n_layers == 1 else d) * d
    if cfg.variant == "graphcast":
        per_layer += 2 * n_edges * (2 * d + cfg.d_edge) * cfg.d_edge
    model_flops = 3.0 * (
        2 * n_nodes * d_in * d + (cfg.n_layers - 1) * per_layer
    )
    return fn, args, model_flops


def recsys_cell(spec, shape_id, shape, mesh):
    cfg = spec.config
    kind = shape["kind"]
    batch = shape["batch"]
    params = autoint.abstract_params(cfg)
    pshard = shd.param_shardings("recsys", params, mesh)
    if kind == "train":
        oc = AdamWConfig()
        opt = jax.eval_shape(lambda p: adamw_init(p, oc), params)
        oshard = {"m": pshard, "v": pshard,
                  "step": shd.replicated(jnp.zeros(()), mesh)}
        batch_specs = autoint.input_specs(cfg, "train", batch)
        bshard = shd.batch_shardings("gnn", batch_specs, mesh)

        def step(p, o, b):
            loss, g = jax.value_and_grad(
                lambda q: autoint.loss_fn(q, b, cfg)
            )(p)
            p, o = adamw_update(g, o, p, oc)
            return p, o, loss

        fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
        args = (params, opt, batch_specs)
    elif kind == "serve":
        batch_specs = autoint.input_specs(cfg, "serve", batch)
        bshard = shd.batch_shardings("gnn", batch_specs, mesh)

        def step(p, b):
            return autoint.forward(p, b, cfg)

        fn = jax.jit(step, in_shardings=(pshard, bshard))
        args = (params, batch_specs)
    else:  # retrieval
        batch_specs = autoint.input_specs(
            cfg, "retrieval", batch, n_candidates=shape["n_candidates"]
        )
        bshard = shd.batch_shardings("gnn", batch_specs, mesh)

        def step(p, b):
            return autoint.retrieval_score(p, b, cfg)

        fn = jax.jit(step, in_shardings=(pshard, bshard))
        args = (params, batch_specs)
    # interaction + MLP flops (embedding lookups are bytes, not flops)
    f, da = cfg.n_fields, cfg.d_attn
    attn_flops = cfg.n_attn_layers * (
        2 * f * (cfg.embed_dim * da * 3) + 2 * f * f * da * 2
    )
    mlp_flops = 2 * sum(
        a * b
        for a, b in zip((f * da,) + cfg.mlp_dims, cfg.mlp_dims + (1,))
    )
    mult = 3.0 if kind == "train" else 1.0
    model_flops = mult * batch * (attn_flops + mlp_flops)
    if kind == "retrieval":
        model_flops += 2.0 * shape["n_candidates"] * da
    return fn, args, model_flops


# ---------------------------------------------------------------------------
# driver


def _cost_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` returns a dict on jax ≥ 0.4.38 but a
    one-element list of dicts on older jaxlibs — normalize to the dict."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost if cost is not None else {}


def _f32_shadow_estimate(hlo: str) -> int:
    """Bytes of f32 buffers that are dtype-shadows of bf16 buffers (same
    dims in both dtypes). Each distinct shadowed shape counted once."""
    import re as _re

    shapes = {"f32": set(), "bf16": set()}
    for m in _re.finditer(r"(f32|bf16)\[([0-9,]+)\]", hlo):
        shapes[m.group(1)].add(m.group(2))
    total = 0
    for dims in shapes["f32"] & shapes["bf16"]:
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n * 4 > 1 << 27:  # only count ≥128 MB twins
            total += n * 4
    return total


def dryrun_cell(arch_id: str, shape_id: str, mesh_kind: str,
                hw: HW = HW()) -> dict:
    spec = configs.get_spec(arch_id)
    shape = spec.shapes[shape_id]
    skip = spec.skips.get(shape_id)
    rec = {
        "arch": arch_id,
        "shape": shape_id,
        "mesh": mesh_kind,
        "shape_params": {k: v for k, v in shape.items()},
    }
    if skip:
        rec.update(status="skipped", reason=skip)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    maker = {"lm": lm_cell, "gnn": gnn_cell, "recsys": recsys_cell}[spec.family]
    t0 = time.time()
    try:
        shd.activate(mesh)
        with mesh:
            fn, args, model_flops = maker(spec, shape_id, shape, mesh)
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
            mem = compiled.memory_analysis()
            cost = _cost_dict(compiled)
            hlo = compiled.as_text()
            probe = None
            if spec.family == "lm" and spec.config.n_layers > 2:
                # scan-body flops correction probe (see _lm_probe_cfg)
                fn2, args2, _ = lm_cell(
                    spec, shape_id, shape, mesh, cfg=_lm_probe_cfg(spec.config)
                )
                compiled2 = fn2.lower(*args2).compile()
                probe = (
                    _cost_dict(compiled2),
                    compiled2.as_text(),
                )
    except Exception as e:  # record failures — they are bugs to fix
        rec.update(
            status="failed",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-4000:],
        )
        return rec
    finally:
        shd.deactivate()
    coll = collective_bytes_from_hlo(hlo, n_dev)
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    shadow = _f32_shadow_estimate(hlo)
    correction = None
    if probe is not None:
        cost2, hlo2 = probe
        L = spec.config.n_layers
        micro = MICROBATCH.get((arch_id, shape_id), 1) if spec.family == "lm" else 1
        lf = max(float(cost2.get("flops", 0.0)) - flops_dev, 0.0)
        lb = max(float(cost2.get("bytes accessed", 0.0)) - bytes_dev, 0.0)
        coll2 = collective_bytes_from_hlo(hlo2, n_dev)
        lc = {
            k: max(coll2[k] - coll[k], 0.0) for k in coll
        }
        correction = {
            "layer_flops_per_device": lf,
            "layer_bytes_per_device": lb,
            "layer_collective_bytes": lc["total"],
            "microbatch_multiplier": micro,
        }
        # the microbatch scan is also counted once by cost_analysis; the
        # optimizer (outside the scan) is counted fully but is negligible
        flops_dev = micro * (flops_dev + (L - 1) * lf)
        bytes_dev = micro * (bytes_dev + (L - 1) * lb)
        coll = {k: micro * (coll[k] + (L - 1) * lc[k]) for k in coll}
    terms = roofline_terms(
        flops_dev, bytes_dev, coll["total"], n_dev, hw, model_flops
    )
    peak_dev_bytes = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    # XLA:CPU wraps every bf16 dot operand in an f32 convert (no native
    # bf16 matmul); the resulting f32 twins of bf16 buffers don't exist on
    # TPU (MXU consumes bf16). `corrected` subtracts one f32 twin per
    # distinct shadowed shape — a conservative TPU-faithful estimate.
    corrected = max(peak_dev_bytes - shadow, 0)
    rec.update(
        status="ok",
        n_devices=n_dev,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_bytes": peak_dev_bytes,
            "fits_16GB": bool(peak_dev_bytes < hw.hbm_bytes),
            "cpu_f32_shadow_bytes": shadow,
            "peak_tpu_corrected_bytes": corrected,
            "fits_16GB_corrected": bool(corrected < hw.hbm_bytes),
        },
        cost={
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            "raw_flops_per_device": float(cost.get("flops", 0.0)),
            "scan_correction": correction,
        },
        collectives=coll,
        roofline=terms,
    )
    return rec


def _palgol_step_plans(algos=("sssp", "wcc", "sv", "chain4"), costs=None) -> dict:
    """Per-step superstep plans (repro.core.plan) for the representative
    programs, under every schedule — what the partitioned executor will
    dispatch, printed so a pod-scale dry-run shows the op-by-op shape of
    each superstep before any device exists. ``costs`` (a ByteCostModel
    instrumented from the pod-scale partition) annotates every plan with
    its modeled wire bytes and adds the byte-aware ``auto`` pick under a
    sparse-request-set regime."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from repro.core import algorithms as alg, compile_program
    from repro.core import plan as plan_mod
    from repro.core.plan import SCHEDULES, program_plan_records
    from repro.graph import generators as G

    small = G.erdos_renyi(64, 4.0, directed=False, weighted=True, seed=0)
    out = {}
    for name in algos:
        init_fields = None
        if name == "chain4":
            init_fields = {"D": jnp.zeros((64,), jnp.int32)}
        cp = compile_program(alg.ALL[name], small, initial_fields=init_fields)
        cell = {
            sched: program_plan_records(cp.step_plans(sched), costs=costs)
            for sched in SCHEDULES
        }
        if costs is not None:
            cell["auto_bytes"] = program_plan_records(
                _dc.replace(cp, byte_costs=costs).step_plans("auto"),
                costs=costs,
            )
        # the §4.3-fused program schedule the executors dispatch by default:
        # merged supersteps + the per-iteration saving, vs the unfused base
        unfused = plan_mod.lower_program(cp.prog, schedule="pull")
        fused = plan_mod.fuse(unfused)
        ub, up, _ = unfused.cost()
        fb, fp, _ = fused.cost()
        cell["fused_program"] = {
            "items": fused.describe(),
            "base": fb,
            "per_iter": {str(k): v for k, v in fp.items()},
            "unfused_base": ub,
            "unfused_per_iter": {str(k): v for k, v in up.items()},
        }
        out[name] = cell
    return out


def palgol_partition_cell(n_shards: int = 256, scale: int = 18) -> dict:
    """Dry-run the partitioned Palgol layout at pod shard counts.

    The partitioner is host-side, so validating the pod-scale layout needs
    no devices at all: partition an R-MAT graph (the paper's power-law
    regime) into one shard per production chip and record balance, halo
    size, projected per-superstep bytes vs the replicated layout, and the
    per-step superstep plans each schedule would dispatch.
    Writes ``experiments/dryrun/palgol_partition.json``.
    """
    from repro.graph import generators as G
    from repro.graph.partition import byte_cost_model, comm_bytes_report

    g = G.rmat(scale, avg_degree=16.0, directed=True, seed=0)
    rec = comm_bytes_report(g, n_shards)
    stats = rec["partition"]
    rec = dict(rec)
    rec["status"] = "ok"
    rec["balance"] = (
        max(stats["pull_edges_per_shard"])
        / max(1.0, stats["n_edges"] / n_shards)
    )
    # byte model instrumented from this pod-scale layout, in the sparse
    # regime (request set = the measured halo — the boundary-active case
    # where the byte-aware auto abandons pull at deep chains)
    costs = byte_cost_model(
        g, n_shards,
        request_set=max(1, stats["halo_total"]),
        combined_request_set=max(1, stats["halo_total"] // 4),
    )
    rec["byte_cost_model"] = {
        "n_vertices": costs.n_vertices,
        "halo_bytes": costs.halo_bytes,
        "request_set": costs.request_set,
        "combined_request_set": costs.combined_request_set,
    }
    rec["step_plans"] = _palgol_step_plans(costs=costs)
    for name, cell in rec["step_plans"].items():
        for sched, steps in cell.items():
            if sched == "fused_program":
                print(
                    f"plan {name} fused program: base={steps['base']} "
                    f"per_iter={steps['per_iter']} (unfused "
                    f"base={steps['unfused_base']} "
                    f"per_iter={steps['unfused_per_iter']})",
                    flush=True,
                )
                for line in steps["items"]:
                    print(f"  {line}", flush=True)
                continue
            for i, s in enumerate(steps):
                print(
                    f"plan {name} step{i} [{sched}->{s['resolved']}] "
                    f"({s['supersteps']} ss, ~{s.get('bytes', 0)/1e3:.1f}KB): "
                    f"{s['ops']}",
                    flush=True,
                )
    path = OUT_DIR / "palgol_partition.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=2))
    red = rec["reduction_vs_replicated"]
    print(
        f"palgol-partition: shards={n_shards} n={stats['n_vertices']} "
        f"e={stats['n_edges']} balance={rec['balance']:.3f} "
        f"halo_total={stats['halo_total']} "
        f"reduction={'inf' if red is None else f'{red:.2f}'}x",
        flush=True,
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--palgol-partition", action="store_true",
                    help="host-side pod-scale partition layout dry-run only")
    ap.add_argument("--shards", type=int, default=256)
    ap.add_argument("--graph-scale", type=int, default=18)
    args = ap.parse_args()

    if args.palgol_partition:
        palgol_partition_cell(args.shards, args.graph_scale)
        return

    archs = configs.all_arch_ids() if (args.all or not args.arch) else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    out_root = Path(args.out)
    n_ok = n_fail = n_skip = 0
    for mesh_kind in meshes:
        for arch in archs:
            spec = configs.get_spec(arch)
            shapes = [args.shape] if args.shape else list(spec.shapes)
            for shape_id in shapes:
                path = out_root / mesh_kind / f"{arch}__{shape_id}.json"
                if args.skip_existing and path.exists():
                    prev = json.loads(path.read_text())
                    if prev.get("status") == "ok":
                        print(f"[cached] {mesh_kind} {arch} {shape_id}")
                        n_ok += 1
                        continue
                print(f"[dryrun] {mesh_kind} {arch} {shape_id} ...", flush=True)
                rec = dryrun_cell(arch, shape_id, mesh_kind)
                path.parent.mkdir(parents=True, exist_ok=True)
                path.write_text(json.dumps(rec, indent=2))
                st = rec["status"]
                n_ok += st == "ok"
                n_fail += st == "failed"
                n_skip += st == "skipped"
                if st == "ok":
                    m = rec["memory"]
                    r = rec["roofline"]
                    print(
                        f"  ok: compile={rec['compile_s']}s "
                        f"peak/dev={m['peak_per_device_bytes']/1e9:.2f}GB "
                        f"fits={m['fits_16GB']} "
                        f"bottleneck={r['bottleneck']} "
                        f"roofline_frac={r.get('roofline_fraction', 0):.3f}",
                        flush=True,
                    )
                    print("  memory_analysis:", rec["memory"], flush=True)
                    print(
                        "  cost_analysis:",
                        {
                            k: f"{v:.3e}"
                            for k, v in rec["cost"].items()
                            if isinstance(v, float)
                        },
                        flush=True,
                    )
                elif st == "failed":
                    print(f"  FAILED: {rec['error']}", flush=True)
                else:
                    print(f"  skipped: {rec['reason']}", flush=True)
    print(f"done: ok={n_ok} failed={n_fail} skipped={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
