"""GNN layers on the shared segment-op substrate (``repro.graph.ops``).

Every layer is "one algorithmic superstep" in the paper's model: gather
neighbor state along edges, segment-reduce by destination, update locally.
The same :func:`repro.graph.ops.segment_reduce` primitive backs the Palgol
codegen and (on TPU) the Pallas ``segment_reduce`` kernel.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.dist.sharding import ALL, constrain
from repro.graph import ops as gops
from repro.models.common import dense_init


def _ce(t):
    """Shard an edge-indexed tensor over every mesh axis."""
    return constrain(t, (ALL,) + (None,) * (t.ndim - 1))


def _mean(vals, dst, n, mask):
    s = gops.mp_segment_reduce(vals, dst, n, "sum", mask=mask)
    cnt = gops.mp_segment_reduce(
        jnp.ones(vals.shape[:1], vals.dtype), dst, n, "sum", mask=mask
    )
    return s / jnp.maximum(cnt[:, None], 1.0)


def init_sage_layer(key, d_in, d_out, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w_self": dense_init(k1, d_in, d_out, dtype),
        "w_nbr": dense_init(k2, d_in, d_out, dtype),
        "b": jnp.zeros((d_out,), dtype),
    }


def sage_layer(p, x, src, dst, emask, n, aggregator="mean"):
    nbr_vals = _ce(gops.mp_gather(x, src))
    if aggregator == "mean":
        agg = _mean(nbr_vals, dst, n, emask)
    else:
        agg = gops.mp_segment_reduce(nbr_vals, dst, n, aggregator, mask=emask)
        if aggregator in ("min", "max"):
            agg = jnp.where(jnp.isfinite(agg), agg, 0.0)
    return jax.nn.relu(x @ p["w_self"] + agg @ p["w_nbr"] + p["b"])


def init_gat_layer(key, d_in, d_out, n_heads, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": dense_init(k1, d_in, n_heads * d_out, dtype),
        "a_src": (jax.random.normal(k2, (n_heads, d_out)) * 0.1).astype(dtype),
        "a_dst": (jax.random.normal(k3, (n_heads, d_out)) * 0.1).astype(dtype),
    }


def gat_layer(p, x, src, dst, emask, n, n_heads, d_out, concat=True):
    h = (x @ p["w"]).reshape(n, n_heads, d_out)
    alpha_src = jnp.einsum("nhd,hd->nh", h, p["a_src"])
    alpha_dst = jnp.einsum("nhd,hd->nh", h, p["a_dst"])
    scores = _ce(jax.nn.leaky_relu(
        gops.mp_gather(alpha_src, src)
        + gops.mp_gather(alpha_dst, dst),
        negative_slope=0.2,
    ))  # [E, H]
    att = _ce(gops.mp_edge_softmax(scores, dst, n, mask=emask))
    vals = _ce(gops.mp_gather(h, src) * att[..., None])  # [E, H, D]
    out = gops.mp_segment_reduce(vals, dst, n, "sum", mask=emask)  # [N, H, D]
    if concat:
        return jax.nn.elu(out.reshape(n, n_heads * d_out))
    return jax.nn.elu(jnp.mean(out, axis=1))


def init_pna_layer(key, d_in, d_out, n_agg, n_scale, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w": dense_init(k1, d_in * (1 + n_agg * n_scale), d_out, dtype),
        "b": jnp.zeros((d_out,), dtype),
        "w_pre": dense_init(k2, d_in, d_in, dtype),
    }


def pna_layer(p, x, src, dst, emask, n, aggregators, scalers, delta):
    msg = _ce(jax.nn.relu(gops.mp_gather(x, src) @ p["w_pre"]))
    deg = gops.mp_segment_reduce(
        jnp.ones(msg.shape[:1], x.dtype), dst, n, "sum", mask=emask
    )
    aggs = []
    mean = _mean(msg, dst, n, emask)
    for a in aggregators:
        if a == "mean":
            aggs.append(mean)
        elif a == "std":
            sq = _mean(jnp.square(msg), dst, n, emask)
            aggs.append(jnp.sqrt(jnp.maximum(sq - jnp.square(mean), 0.0) + 1e-5))
        else:
            v = gops.mp_segment_reduce(msg, dst, n, a, mask=emask)
            aggs.append(jnp.where(jnp.isfinite(v), v, 0.0))
    agg = jnp.stack(aggs, axis=1)  # [N, A, D]
    logd = jnp.log1p(deg)[:, None, None]
    outs = []
    for s in scalers:
        if s == "identity":
            outs.append(agg)
        elif s == "amplification":
            outs.append(agg * (logd / delta))
        elif s == "attenuation":
            outs.append(agg * (delta / jnp.maximum(logd, 1e-3)))
    feats = jnp.concatenate(
        [x] + [o.reshape(n, -1) for o in outs], axis=-1
    )
    return jax.nn.relu(feats @ p["w"] + p["b"])



def _fused_mesh():
    return gops._mp_mesh()


def pna_layer_fused(p, x, src, dst, emask, n, aggregators, scalers, delta):
    """PNA with all aggregations in ONE shard_map region: the node state is
    replicated once per layer (instead of once per mp_* call), which is the
    peak-memory lever on 62M-edge graphs. Falls back to the composable
    version off-mesh."""
    mesh, daxes, n_data = _fused_mesh()
    if mesh is None or n_data == 1 or src.shape[0] % n_data != 0:
        return pna_layer(p, x, src, dst, emask, n, aggregators, scalers, delta)
    from jax.sharding import PartitionSpec as P

    d = gops._dspec(daxes)

    n_loc = n // n_data

    def local(x_full, w_pre, src_l, dst_l, m_l):
        msg = jax.nn.relu(gops.gather(x_full, src_l) @ w_pre)
        # flat shard index in daxes order (matches out_spec dim-0 layout)
        flat = None
        for a in daxes:
            ia = jax.lax.axis_index(a)
            flat = ia if flat is None else flat * mesh.shape[a] + ia
        start = flat * n_loc

        def rs(v):  # sum-reductions return node-sharded via reduce-scatter
            return jax.lax.psum_scatter(v, daxes, scatter_dimension=0,
                                        tiled=True)

        def shard_slice(v):  # max/min: allreduce then keep the local shard
            return jax.lax.dynamic_slice_in_dim(v, start, n_loc, 0)

        outs = {}
        ones = jnp.ones(msg.shape[:1] + (1,), msg.dtype)
        outs["cnt"] = rs(gops.segment_reduce(ones, dst_l, n, "sum", mask=m_l))
        outs["sum"] = rs(gops.segment_reduce(msg, dst_l, n, "sum", mask=m_l))
        if "std" in aggregators:
            outs["sumsq"] = rs(
                gops.segment_reduce(jnp.square(msg), dst_l, n, "sum", mask=m_l)
            )
        if "max" in aggregators:
            outs["max"] = shard_slice(gops._diff_pminmax(
                gops.segment_reduce(msg, dst_l, n, "max", mask=m_l), daxes, True
            ))
        if "min" in aggregators:
            outs["min"] = shard_slice(gops._diff_pminmax(
                gops.segment_reduce(msg, dst_l, n, "min", mask=m_l), daxes,
                False,
            ))
        return tuple(outs[k] for k in sorted(outs))

    keys = ["cnt", "sum"]
    if "std" in aggregators:
        keys.append("sumsq")
    if "max" in aggregators:
        keys.append("max")
    if "min" in aggregators:
        keys.append("min")
    keys = sorted(keys)
    res = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None), P(None, None), P(d), P(d), P(d)),
        out_specs=tuple(P(d, None) for _ in keys),
        check_vma=False,
    )(x, p["w_pre"], src, dst, emask)
    r = dict(zip(keys, res))
    cnt = jnp.maximum(r["cnt"][:, :1], 1.0)
    mean = r["sum"] / cnt
    deg = r["cnt"][:, 0]
    aggs = []
    for a in aggregators:
        if a == "mean":
            aggs.append(mean)
        elif a == "std":
            sq = r["sumsq"] / cnt
            aggs.append(jnp.sqrt(jnp.maximum(sq - jnp.square(mean), 0.0) + 1e-5))
        elif a == "max":
            aggs.append(jnp.where(jnp.isfinite(r["max"]), r["max"], 0.0))
        elif a == "min":
            aggs.append(jnp.where(jnp.isfinite(r["min"]), r["min"], 0.0))
    agg = constrain(jnp.stack(aggs, axis=1), (ALL, None, None))
    logd = jnp.log1p(deg)[:, None, None]
    outs = []
    for s in scalers:
        if s == "identity":
            outs.append(agg)
        elif s == "amplification":
            outs.append(agg * (logd / delta))
        elif s == "attenuation":
            outs.append(agg * (delta / jnp.maximum(logd, 1e-3)))
    feats = jnp.concatenate([x] + [o.reshape(n, -1) for o in outs], axis=-1)
    return jax.nn.relu(feats @ p["w"] + p["b"])


def mpnn_layer_fused(p, x, e_feat, src, dst, emask, n):
    """GraphCast block with gathers + edge MLP + aggregation fused into one
    shard_map region: one node-state replication per layer."""
    mesh, daxes, n_data = _fused_mesh()
    if mesh is None or n_data == 1 or src.shape[0] % n_data != 0:
        return mpnn_layer(p, x, e_feat, src, dst, emask, n)
    from jax.sharding import PartitionSpec as P

    d = gops._dspec(daxes)

    def local(x_full, e_loc, w1, w2, src_l, dst_l, m_l):
        cat = jnp.concatenate(
            [gops.gather(x_full, src_l), gops.gather(x_full, dst_l), e_loc],
            axis=-1,
        )
        e_new = jax.nn.silu(cat @ w1) @ w2 + e_loc
        # reduce-scatter: each device keeps only its node shard of the
        # aggregate — no replicated [N, D] buffer ever materializes
        agg = jax.lax.psum_scatter(
            gops.segment_reduce(e_new, dst_l, n, "sum", mask=m_l),
            daxes, scatter_dimension=0, tiled=True,
        )
        return e_new, agg

    e_new, agg = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(None, None), P(d, None), P(None, None), P(None, None),
            P(d), P(d), P(d),
        ),
        out_specs=(P(d, None), P(d, None)),
        check_vma=False,
    )(x, e_feat, p["edge_w1"], p["edge_w2"], src, dst, emask)
    x_new = (
        jax.nn.silu(jnp.concatenate([x, agg], axis=-1) @ p["node_w1"])
        @ p["node_w2"]
        + x
    )
    return x_new, e_new


def init_mpnn_layer(key, d_node, d_edge, dtype):
    """GraphCast-style interaction-network block (edge+node MLPs)."""
    ks = jax.random.split(key, 4)
    d_cat = 2 * d_node + d_edge
    return {
        "edge_w1": dense_init(ks[0], d_cat, d_edge, dtype),
        "edge_w2": dense_init(ks[1], d_edge, d_edge, dtype),
        "node_w1": dense_init(ks[2], d_node + d_edge, d_node, dtype),
        "node_w2": dense_init(ks[3], d_node, d_node, dtype),
    }


def mpnn_layer(p, x, e_feat, src, dst, emask, n):
    """x: [N, Dn]; e_feat: [E, De] → (x', e') with residuals (GraphCast)."""
    cat = _ce(jnp.concatenate(
        [
            gops.mp_gather(x, src),
            gops.mp_gather(x, dst),
            e_feat,
        ],
        axis=-1,
    ))
    e_new = _ce(jax.nn.silu(cat @ p["edge_w1"]) @ p["edge_w2"] + e_feat)
    agg = gops.mp_segment_reduce(e_new, dst, n, "sum", mask=emask)
    x_new = (
        jax.nn.silu(jnp.concatenate([x, agg], axis=-1) @ p["node_w1"])
        @ p["node_w2"]
        + x
    )
    return x_new, e_new
