"""GNN models: init, loss, train/serve steps for all four assigned archs.

Batch contract (full-graph modes):
    {"x": [N, Din], "src": [E], "dst": [E], "emask": [E],
     "labels": [N] or [N, n_out], "lmask": [N]}
Batched small graphs (``molecule``) use the disjoint-union layout with a
``graph_id`` [N] vector and graph-level labels [B].
Sampled minibatch (``minibatch_lg``) uses padded sampler blocks:
    {"seed_x": [B, Din], "hop0_x": [B*f0, Din], "hop0_mask": [B, f0],
     "hop1_x": [B*f0*f1, Din], "hop1_mask": [B*f0, f1], "labels": [B]}
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.dist.sharding import ALL, constrain
from repro.graph import ops as gops
from repro.models import common
from repro.models.common import dense_init
from repro.models.gnn import layers as L
from repro.models.gnn.config import GNNConfig


# ---------------------------------------------------------------------------
# init


def init(key, cfg: GNNConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, cfg.n_layers + 3)
    p: Dict[str, Any] = {"layers": []}
    d = cfg.d_hidden
    if cfg.variant == "sage":
        dims = [cfg.d_in] + [d] * cfg.n_layers
        p["layers"] = [
            L.init_sage_layer(ks[i], dims[i], dims[i + 1], dtype)
            for i in range(cfg.n_layers)
        ]
    elif cfg.variant == "gat":
        dims = [cfg.d_in] + [d * cfg.n_heads] * cfg.n_layers
        p["layers"] = [
            L.init_gat_layer(ks[i], dims[i], d, cfg.n_heads, dtype)
            for i in range(cfg.n_layers)
        ]
    elif cfg.variant == "pna":
        na, nsc = len(cfg.pna_aggregators), len(cfg.pna_scalers)
        # first layer maps d_in -> d; the uniform tail is stacked for scan
        p["layer0"] = L.init_pna_layer(ks[0], cfg.d_in, d, na, nsc, dtype)
        if cfg.n_layers > 1:
            p["layers"] = common.stack_init(
                ks[1], cfg.n_layers - 1,
                lambda k: L.init_pna_layer(k, d, d, na, nsc, dtype),
            )
        else:
            p["layers"] = None
    elif cfg.variant == "graphcast":
        de = max(cfg.d_edge, d)
        p["encode_node"] = dense_init(ks[-3], cfg.d_in, d, dtype)
        p["encode_edge"] = dense_init(ks[-2], 1, de, dtype)  # from edge weight
        # identical processor blocks: stacked + lax.scan (buffer reuse
        # across layers — unrolled layers keep 16 sets of temps alive)
        p["layers"] = common.stack_init(
            ks[0], cfg.n_layers, lambda k: L.init_mpnn_layer(k, d, de, dtype)
        )
    else:
        raise ValueError(cfg.variant)
    d_final = d * cfg.n_heads if cfg.variant == "gat" else d
    p["head"] = dense_init(ks[-1], d_final, cfg.n_out, dtype)
    return p


def abstract_params(cfg: GNNConfig):
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# full-graph forward


def forward(params, batch, cfg: GNNConfig):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = batch["x"].astype(cdt)
    src, dst, emask = batch["src"], batch["dst"], batch["emask"]
    n = x.shape[0]

    def _c(t):  # shard node/edge activations over every mesh axis
        return constrain(t, (ALL,) + (None,) * (t.ndim - 1))

    x = _c(x)
    maybe_ckpt = jax.checkpoint if cfg.remat else (lambda f: f)
    if cfg.variant == "graphcast":
        cast_params = jax.tree_util.tree_map(
            lambda p: p.astype(cdt) if p.dtype == jnp.float32 else p, params
        )
        h = _c(jax.nn.silu(x @ cast_params["encode_node"]))
        w = batch.get("ew", jnp.ones(src.shape, x.dtype)).astype(cdt)
        e = _c(jax.nn.silu(w[:, None] @ cast_params["encode_edge"]))  # [E, De]

        def gc_body(carry, lp):
            h, e = carry
            h = jax.lax.optimization_barrier(h)
            h, e = L.mpnn_layer_fused(lp, h, e, src, dst, emask, n)
            return (_c(h), _c(e)), None

        body = maybe_ckpt(gc_body)
        (h, e), _ = jax.lax.scan(body, (h, e), cast_params["layers"])
        return (h @ cast_params["head"]).astype(jnp.float32)

    if cfg.variant == "pna":
        # cast params to the compute dtype (else bf16 x promotes back to f32)
        cparams = jax.tree_util.tree_map(
            lambda p: p.astype(cdt) if p.dtype == jnp.float32 else p, params
        )

        def pna_apply(lp, h):
            return _c(L.pna_layer_fused(
                lp, h, src, dst, emask, n,
                cfg.pna_aggregators, cfg.pna_scalers, cfg.pna_delta,
            ))

        h = maybe_ckpt(pna_apply)(cparams["layer0"], x)
        if cparams.get("layers") is not None:
            def pna_body(h, lp):
                h = jax.lax.optimization_barrier(h)
                return maybe_ckpt(pna_apply)(lp, h), None

            h, _ = jax.lax.scan(pna_body, h, cparams["layers"])
        return (h @ cparams["head"]).astype(jnp.float32)

    def one_layer(lp, h):
        if cfg.variant == "sage":
            h = L.sage_layer(lp, h, src, dst, emask, n, cfg.aggregator)
        elif cfg.variant == "gat":
            h = L.gat_layer(lp, h, src, dst, emask, n, cfg.n_heads,
                            cfg.d_hidden)
        return _c(h)

    one_layer = maybe_ckpt(one_layer)
    h = x
    for lp in params["layers"]:
        h = one_layer(lp, h)
    return (h @ params["head"]).astype(jnp.float32)


def loss_fn(params, batch, cfg: GNNConfig):
    out = forward(params, batch, cfg)
    if cfg.task == "regression":
        if "graph_id" in batch:
            # batched small graphs: per-graph property regression
            gid = batch["graph_id"]
            n_graphs = batch["labels"].shape[0]
            pooled = gops.segment_reduce(out, gid, n_graphs, "sum")
            cnt = gops.segment_reduce(
                jnp.ones(out.shape[:1], out.dtype), gid, n_graphs, "sum"
            )
            pred = pooled / jnp.maximum(cnt[:, None], 1.0)
            return jnp.mean(jnp.square((pred - batch["labels"]).astype(jnp.float32)))
        err = (out - batch["labels"]).astype(jnp.float32)
        m = batch.get("lmask")
        if m is not None:
            err = err * m[:, None]
            denom = jnp.maximum(jnp.sum(m), 1.0) * out.shape[-1]
            return jnp.sum(jnp.square(err)) / denom
        return jnp.mean(jnp.square(err))
    if cfg.task == "graph_class":
        # disjoint-union batching: mean-pool nodes per graph
        gid = batch["graph_id"]
        n_graphs = batch["labels"].shape[0]
        pooled = gops.segment_reduce(out, gid, n_graphs, "sum")
        cnt = gops.segment_reduce(
            jnp.ones(out.shape[:1], out.dtype), gid, n_graphs, "sum"
        )
        logits = pooled / jnp.maximum(cnt[:, None], 1.0)
        return common.softmax_cross_entropy(logits, batch["labels"])
    # node classification with a labeled-node mask
    logits = out.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    per_node = lse - gold
    m = batch.get("lmask")
    if m is not None:
        per_node = per_node * m
        return jnp.sum(per_node) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(per_node)


# ---------------------------------------------------------------------------
# sampled-minibatch SAGE (GraphSAGE's native training mode)


def sage_minibatch_forward(params, batch, cfg: GNNConfig):
    """Two-hop sampled forward with padded blocks (fanouts f0, f1)."""
    assert cfg.variant == "sage" and len(cfg.fanouts) == 2
    f0, f1 = cfg.fanouts
    seed_x = batch["seed_x"]  # [B, Din]
    hop0_x = batch["hop0_x"]  # [B*f0, Din]
    hop1_x = batch["hop1_x"]  # [B*f0*f1, Din]
    m0 = batch["hop0_mask"]  # [B, f0]
    m1 = batch["hop1_mask"]  # [B*f0, f1]
    b = seed_x.shape[0]
    l1, l2 = params["layers"]

    def masked_mean(vals, mask):
        w = mask[..., None].astype(vals.dtype)
        return jnp.sum(vals * w, axis=-2) / jnp.maximum(
            jnp.sum(w, axis=-2), 1.0
        )

    # layer 1 at hop-0 nodes: aggregate their sampled hop-1 neighbors
    nbr1 = masked_mean(hop1_x.reshape(b * f0, f1, -1), m1)
    h0 = jax.nn.relu(hop0_x @ l1["w_self"] + nbr1 @ l1["w_nbr"] + l1["b"])
    # layer 1 at seeds (self transform with their own neighbors = hop0 raw)
    nbr_seed = masked_mean(hop0_x.reshape(b, f0, -1), m0)
    h_seed = jax.nn.relu(seed_x @ l1["w_self"] + nbr_seed @ l1["w_nbr"] + l1["b"])
    # layer 2 at seeds: aggregate hop-0 hidden states
    nbr2 = masked_mean(h0.reshape(b, f0, -1), m0)
    h = jax.nn.relu(h_seed @ l2["w_self"] + nbr2 @ l2["w_nbr"] + l2["b"])
    return h @ params["head"]


def sage_minibatch_loss(params, batch, cfg: GNNConfig):
    logits = sage_minibatch_forward(params, batch, cfg)
    return common.softmax_cross_entropy(logits, batch["labels"])


# ---------------------------------------------------------------------------
# dry-run input specs


def input_specs(cfg: GNNConfig, shape_kind: str, **dims):
    f32, i32 = jnp.float32, jnp.int32
    if shape_kind == "full_graph":
        n, e = dims["n_nodes"], dims["n_edges"]
        d = dims.get("d_feat", cfg.d_in)
        spec = {
            "x": jax.ShapeDtypeStruct((n, d), f32),
            "src": jax.ShapeDtypeStruct((e,), i32),
            "dst": jax.ShapeDtypeStruct((e,), i32),
            "emask": jax.ShapeDtypeStruct((e,), jnp.bool_),
        }
        if cfg.task == "regression":
            spec["labels"] = jax.ShapeDtypeStruct((n, cfg.n_out), f32)
        else:
            spec["labels"] = jax.ShapeDtypeStruct((n,), i32)
        spec["lmask"] = jax.ShapeDtypeStruct((n,), f32)
        return spec
    if shape_kind == "minibatch":
        b = dims["batch_nodes"]
        f0, f1 = cfg.fanouts
        d = dims.get("d_feat", cfg.d_in)
        return {
            "seed_x": jax.ShapeDtypeStruct((b, d), f32),
            "hop0_x": jax.ShapeDtypeStruct((b * f0, d), f32),
            "hop0_mask": jax.ShapeDtypeStruct((b, f0), jnp.bool_),
            "hop1_x": jax.ShapeDtypeStruct((b * f0 * f1, d), f32),
            "hop1_mask": jax.ShapeDtypeStruct((b * f0, f1), jnp.bool_),
            "labels": jax.ShapeDtypeStruct((b,), i32),
        }
    if shape_kind == "batched_graphs":
        b, n, e = dims["batch"], dims["n_nodes"], dims["n_edges"]
        d = dims.get("d_feat", cfg.d_in)
        labels = (
            jax.ShapeDtypeStruct((b, cfg.n_out), f32)
            if cfg.task == "regression"
            else jax.ShapeDtypeStruct((b,), i32)
        )
        return {
            "x": jax.ShapeDtypeStruct((b * n, d), f32),
            "src": jax.ShapeDtypeStruct((b * e,), i32),
            "dst": jax.ShapeDtypeStruct((b * e,), i32),
            "emask": jax.ShapeDtypeStruct((b * e,), jnp.bool_),
            "graph_id": jax.ShapeDtypeStruct((b * n,), i32),
            "labels": labels,
        }
    raise ValueError(shape_kind)
