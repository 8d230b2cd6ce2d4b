"""Mixture-of-Experts FFN with scatter-based (sort-free) token dispatch.

The dispatch is deliberately built on the same gather/scatter-with-combiner
primitives as the Pregel substrate (see DESIGN.md §5): token→expert routing
is a bipartite message exchange with a sum combiner on the way back.

Pipeline (per layer, tokens flattened to T = B·S):
  1. router logits [T, E] (fp32) → top-k gates (softmax over chosen k);
  2. position-in-expert via a capped running count (argsort-free cumsum on
     one-hot columns is O(T·E); we instead sort by expert id — O(T·k log) —
     which XLA lowers to an efficient key-value sort on TPU);
  3. scatter token activations into a capacity-padded expert buffer
     [E, C, D] (slots beyond capacity are dropped — standard GShard policy);
  4. per-expert SwiGLU via batched einsum [E, C, D] × [E, D, F];
  5. gather back + combine with gate weights (segment-sum by token id).

Shared experts (DeepSeekMoE) are a dense SwiGLU over all tokens, added in.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import BATCH, constrain
from repro.models.transformer.config import MoEConfig


def init_moe_params(key, d_model: int, mcfg: MoEConfig, dtype):
    ks = jax.random.split(key, 5)
    e, f = mcfg.n_experts, mcfg.d_ff_expert
    s = 1.0 / math.sqrt(d_model)
    params = {
        "router": (jax.random.normal(ks[0], (d_model, e)) * s).astype(jnp.float32),
        "w1": (jax.random.normal(ks[1], (e, d_model, f)) * s).astype(dtype),
        "w3": (jax.random.normal(ks[2], (e, d_model, f)) * s).astype(dtype),
        "w2": (
            jax.random.normal(ks[3], (e, f, d_model)) * (1.0 / math.sqrt(f))
        ).astype(dtype),
    }
    if mcfg.n_shared_experts:
        sf = mcfg.shared_ff
        k1, k2, k3 = jax.random.split(ks[4], 3)
        params["shared"] = {
            "w1": (jax.random.normal(k1, (d_model, sf)) * s).astype(dtype),
            "w3": (jax.random.normal(k2, (d_model, sf)) * s).astype(dtype),
            "w2": (
                jax.random.normal(k3, (sf, d_model)) * (1.0 / math.sqrt(sf))
            ).astype(dtype),
        }
    return params


def capacity(n_tokens: int, mcfg: MoEConfig) -> int:
    c = int(
        math.ceil(n_tokens * mcfg.top_k * mcfg.capacity_factor / mcfg.n_experts)
    )
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def route(
    x: jax.Array, router_w: jax.Array, mcfg: MoEConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. Returns (expert_idx [T,k], gate [T,k], aux_loss)."""
    logits = (x.astype(jnp.float32) @ router_w).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, mcfg.top_k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], mcfg.n_experts, dtype=jnp.float32),
        axis=0,
    )
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * mcfg.n_experts
    return expert_idx, gate, aux


def dispatch_indices(expert_idx: jax.Array, n_experts: int, cap: int):
    """Position of each (token, slot) within its expert, via sort.

    Returns (pos [T*k], keep [T*k]): pos < cap are the kept slots.
    """
    flat = expert_idx.reshape(-1)  # [T*k]
    tk = flat.shape[0]
    order = jnp.argsort(flat)  # stable: groups tokens by expert
    sorted_e = flat[order]
    # rank within the sorted array minus the start offset of the expert group
    counts = jnp.bincount(flat, length=n_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(tk) - starts[sorted_e]
    pos = jnp.zeros((tk,), jnp.int32).at[order].set(rank.astype(jnp.int32))
    keep = pos < cap
    return pos, keep


def moe_ffn(x: jax.Array, params, mcfg: MoEConfig):
    """x: [T, D] flattened tokens → (y [T, D], aux loss).

    Under an active mesh with a ``model`` axis this routes through the
    expert-parallel shard_map dispatch (:func:`moe_ffn_ep`) — GSPMD cannot
    partition the dispatch scatter (arbitrary destination rows), so the
    scatter/gather runs *manually local* per (data, expert) shard and only
    the EP combine all-reduce crosses the wire. Without a mesh (smoke
    tests, oracle comparisons) the plain single-device path runs.
    """
    from repro.dist import sharding as shd

    mesh = shd._ACTIVE_MESH
    if mesh is not None and "model" in mesh.shape:
        n_model = mesh.shape["model"]
        daxes = tuple(
            a for a in ("pod", "data") if a in mesh.shape
        )
        n_data = 1
        for a in daxes:
            n_data *= mesh.shape[a]
        if (
            mcfg.n_experts % n_model == 0
            and x.shape[0] % n_data == 0
        ):
            return moe_ffn_ep(x, params, mcfg, mesh, daxes, n_data, n_model)
    return _moe_ffn_local(x, params, mcfg)


def _moe_ffn_local(x: jax.Array, params, mcfg: MoEConfig):
    t, d = x.shape
    e, k = mcfg.n_experts, mcfg.top_k
    cap = capacity(t, mcfg)
    expert_idx, gate, aux = route(x, params["router"], mcfg)
    pos, keep = dispatch_indices(expert_idx, e, cap)

    flat_e = expert_idx.reshape(-1)  # [T*k]
    token_id = jnp.repeat(jnp.arange(t), k)  # [T*k]
    # scatter tokens into [E, C, D] (dropped slots fall out of range)
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)  # OOR sentinel
    buf = jnp.zeros((e * cap, d), x.dtype)
    gathered = constrain(x[token_id], (BATCH, None))  # [T*k, D]
    buf = buf.at[slot].add(gathered, mode="drop")
    expert_in = constrain(buf.reshape(e, cap, d), ("model", None, None))

    # per-expert SwiGLU (batched over experts; E sharded = expert parallel)
    h = jnp.einsum("ecd,edf->ecf", expert_in, params["w1"])
    g = jnp.einsum("ecd,edf->ecf", expert_in, params["w3"])
    h = jax.nn.silu(h) * g
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w2"])
    expert_out = constrain(expert_out, ("model", None, None))

    # gather back and combine with gates (segment-sum by token)
    out_slots = expert_out.reshape(e * cap, d)
    vals = jnp.take(out_slots, jnp.minimum(slot, e * cap - 1), axis=0)
    vals = vals * (gate.reshape(-1)[:, None] * keep[:, None]).astype(x.dtype)
    vals = constrain(vals, (BATCH, None))
    y = jnp.zeros((t, d), x.dtype).at[token_id].add(vals)
    y = constrain(y, (BATCH, None))

    if "shared" in params:
        sh = params["shared"]
        hshared = jax.nn.silu(x @ sh["w1"]) * (x @ sh["w3"])
        y = y + hshared @ sh["w2"]
    return y, aux


# ---------------------------------------------------------------------------
# expert-parallel dispatch (shard_map): local scatter, EP-combine all-reduce


def moe_ffn_ep(x, params, mcfg: MoEConfig, mesh, daxes, n_data, n_model):
    """Production EP flow (GShard-style, TPU-native):

    1. **dispatch** (shard_map, fully manual): every (data, model) shard
       routes its local tokens, keeps the experts it owns (E/n_model), and
       scatters *locally* into [E_loc, C_loc, D] — zero collectives;
    2. **expert compute** (pjit): batched SwiGLU on [E(model), C(data), D];
       C stays data-sharded (it's a batch dim of the einsum), weights
       all-gather only their own model-shard slice;
    3. **combine** (shard_map): local gather from owned experts, gate-mix,
       then one psum over `model` — the EP combine all-reduce, the only
       wire traffic of the dispatch.
    """
    from jax.sharding import PartitionSpec as P

    t, d = x.shape
    e, k = mcfg.n_experts, mcfg.top_k
    e_loc = e // n_model
    t_loc = t // n_data
    cap_loc = capacity(t_loc, mcfg)
    dspec = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)

    def dispatch_local(x_loc, router):
        eidx, gate, aux = route(x_loc, router, mcfg)  # [T_loc, k]
        pos, keep = dispatch_indices(eidx, e, cap_loc)
        m_idx = jax.lax.axis_index("model")
        e_local = eidx - m_idx * e_loc  # [T_loc, k]
        mine = (e_local >= 0) & (e_local < e_loc) & keep.reshape(t_loc, k)
        slot = jnp.where(
            mine, e_local * cap_loc + pos.reshape(t_loc, k), e_loc * cap_loc
        )
        buf = jnp.zeros((e_loc * cap_loc, d), x_loc.dtype)
        # one scatter per routing slot: updates stay [T_loc, D] instead of
        # materializing the k×-expanded [T_loc·k, D] gather
        for j in range(k):
            buf = buf.at[slot[:, j]].add(x_loc, mode="drop")
        aux = jax.lax.pmean(aux, daxes) if daxes else aux
        return (
            buf.reshape(e_loc, cap_loc, d),
            eidx,
            gate,
            pos,
            keep,
            aux,
        )

    buf, eidx, gate, pos, keep, aux = jax.shard_map(
        dispatch_local,
        mesh=mesh,
        in_specs=(P(dspec, None), P(None, None)),
        out_specs=(
            P("model", dspec, None),
            P(dspec, None),
            P(dspec, None),
            P(dspec),
            P(dspec),
            P(),
        ),
        check_vma=False,
    )(x, params["router"])

    # --- expert compute (pjit; E model-sharded, C data-sharded) ----------
    h = jnp.einsum("ecd,edf->ecf", buf, params["w1"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    h = constrain(jax.nn.silu(h) * g, ("model", BATCH, None))
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w2"])
    expert_out = constrain(expert_out, ("model", BATCH, None))

    def combine_local(eout_loc, eidx, gate, pos, keep):
        # eout_loc [E_loc, cap_loc, D]
        m_idx = jax.lax.axis_index("model")
        e_local = eidx - m_idx * e_loc  # [T_loc, k]
        mine = (e_local >= 0) & (e_local < e_loc) & keep.reshape(t_loc, k)
        slot = jnp.where(
            mine,
            e_local * cap_loc + pos.reshape(t_loc, k),
            e_loc * cap_loc - 1,
        )
        rows = eout_loc.reshape(e_loc * cap_loc, d)
        y_partial = jnp.zeros((t_loc, d), rows.dtype)
        for j in range(k):  # per-slot gather keeps peaks at [T_loc, D]
            vals = jnp.take(rows, slot[:, j], axis=0)
            w = (gate[:, j] * mine[:, j]).astype(vals.dtype)
            y_partial = y_partial + vals * w[:, None]
        return jax.lax.psum(y_partial, "model")  # EP combine

    y = jax.shard_map(
        combine_local,
        mesh=mesh,
        in_specs=(
            P("model", dspec, None),
            P(dspec, None),
            P(dspec, None),
            P(dspec),
            P(dspec),
        ),
        out_specs=P(dspec, None),
        check_vma=False,
    )(expert_out, eidx, gate, pos, keep)

    if "shared" in params:
        sh = params["shared"]
        hshared = jax.nn.silu(x @ sh["w1"]) * (x @ sh["w3"])
        y = y + hshared @ sh["w2"]
    return y, aux
