"""Halo-exchange collectives for partitioned vertex state (shard_map body).

Every function here runs *inside* a ``shard_map`` over a 1-D ``("shard",)``
mesh; arguments are per-shard blocks (no leading ``[S]`` dimension). Three
communication primitives cover all of Palgol's remote data access:

``halo_exchange``
    Static ghost reads: the owner gathers the boundary values its neighbors
    need (``send_local``), one ``all_to_all`` moves them, the reader
    scatters them into its ghost buffer (``recv_pos``). Per superstep this
    moves only the halo — O(boundary), not O(N) — which is the whole point
    of the subsystem. Used for neighborhood communication (``F[e.id]``),
    whose access set is the static edge structure.

``gather_global``
    Dynamic one-sided reads at arbitrary global vertex ids (chain access:
    ``D[D[u]]``): requests are bucketed by owner, one ``all_to_all`` ships
    the request ids, owners gather locally, a second ``all_to_all`` ships
    the replies. Pull-mode pointer doubling calls this once per doubling
    round — the request set ("the halo") is rebuilt from the *current*
    indirection field each round, exactly the paper's remote-read staging
    but with partitioned instead of replicated state.

``scatter_reduce``
    Remote writes (``remote F[t] op= v``): each shard pre-combines its
    messages into an identity-filled ``[S·v_max]`` buffer, then a
    reduce-scatter (``psum_scatter`` for ``sum``; ``all_to_all`` + a local
    tree-combine for the other monoids) lands each owner's combined delta.
    Targets are data-dependent, so unlike ``halo_exchange`` this pays
    O(N/S·S) worst-case — the price of Palgol's arbitrary remote writes.

Each collective runs under a ``jax.named_scope`` of its own
(``halo_exchange``, ``gather_global/request``, ``gather_global/reply``,
``scatter_reduce``), inside the plan item's ``palgol/…`` scope, so that a
device trace tells them apart. When a collective is traced, the bytes
its operand carries per chip are recorded (:func:`repro.trace.tally`,
events ``/palgol/comm/<primitive>/<padded|payload>``): ``padded`` is the
operand as its static shape has it, what the collective moves;
``payload`` is what an exchange of only the live rows would carry — a
reader's ``n_ghost`` ghost rows for ``halo_exchange``, one request id and
one reply per read for ``gather_global``, one (target id, value) pair per
message for ``scatter_reduce``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import trace
from repro.graph import ops as gops

AXIS = "shard"


def _row_bytes(x: jax.Array) -> int:
    """Bytes of one row (everything past the leading dim) of ``x``."""
    return math.prod(x.shape[1:]) * x.dtype.itemsize


def _carried(primitive: str, padded: int, payload: int) -> None:
    """Record the bytes a collective of ``primitive`` carries per chip
    (see the module doc); called while the collective is traced."""
    trace.tally(f"comm/{primitive}/padded", padded)
    trace.tally(f"comm/{primitive}/payload", payload)


def halo_exchange(
    x: jax.Array,  # [v_max, ...] per-shard field block
    send_local: jax.Array,  # i32[S, Hp] owner-local rows to send, per reader
    recv_pos: jax.Array,  # i32[S, Hp] ghost-buffer slots, per owner
    n_ghost: int,
    axis: str = AXIS,
) -> jax.Array:
    """Static halo gather → ghost values ``[n_ghost, ...]`` for this shard."""
    if n_ghost == 0:
        return jnp.zeros((0,) + x.shape[1:], x.dtype)
    vals = gops.gather(x, send_local)  # [S, Hp, ...] (pad rows clip: unread)
    _carried("halo_exchange", vals.shape[0] * vals.shape[1] * _row_bytes(x),
             n_ghost * _row_bytes(x))
    with jax.named_scope("halo_exchange"):
        recv = jax.lax.all_to_all(vals, axis, split_axis=0, concat_axis=0)
    ghost = jnp.zeros((n_ghost + 1,) + x.shape[1:], x.dtype)
    ghost = ghost.at[recv_pos].set(recv, mode="drop")
    return ghost[:n_ghost]


def _owner_of(idx: jax.Array, starts: jax.Array, n_shards: int) -> jax.Array:
    """Owner shard of each (already clipped) global vertex id."""
    return jnp.clip(
        jnp.searchsorted(starts, idx, side="right") - 1, 0, n_shards - 1
    ).astype(jnp.int32)


def _owner_and_slot(idx: jax.Array, starts: jax.Array, n_shards: int):
    """Owner shard and within-bucket slot for each (clipped) global id."""
    owner = _owner_of(idx, starts, n_shards)
    onehot = owner[:, None] == jnp.arange(n_shards, dtype=jnp.int32)[None, :]
    slot = (
        jnp.take_along_axis(
            jnp.cumsum(onehot.astype(jnp.int32), axis=0), owner[:, None], axis=1
        )[:, 0]
        - 1
    )
    return owner, slot


def gather_global(
    x: jax.Array,  # [v_max, ...] per-shard field block
    idx: jax.Array,  # i32[K] global vertex ids (may include the sentinel N)
    starts: jax.Array,  # i32[S+1] owner map (replicated)
    n_vertices: int,
    v_max: int,
    fill=None,
    axis: str = AXIS,
    dedup: bool = True,
) -> jax.Array:
    """Dynamic read of ``field[idx]`` across shards (request/reply).

    Matches :func:`repro.graph.ops.gather` semantics: with ``fill=None``
    out-of-range ids clip (read vertex ``N-1``); otherwise they read
    ``fill``. Two ``all_to_all`` rounds, ``2·S·K`` values of traffic per
    shard — the honest wire cost of data-dependent remote reads.

    ``dedup=True`` (default) combines duplicate requests before bucketing
    — one request slot and one reply per *distinct* target id (Pregel
    message combining on the request side; replies fan back out through
    the inverse permutation at the requester). The exchange shapes stay
    static, but every duplicate collapses to the padding sentinel, so the
    live payload shrinks to the combined request set — what the push byte
    model (:class:`repro.core.plan.ByteCostModel.combined_request_set`)
    charges for.
    """
    (k,) = idx.shape
    n_shards = starts.shape[0] - 1
    if n_shards == 1:
        return gops.gather(x, jnp.where(idx >= n_vertices, v_max, idx), fill)
    if dedup and k > 1:
        uniq, inv = jnp.unique(
            idx, return_inverse=True, size=k, fill_value=n_vertices
        )
        vals = gather_global(
            x, uniq.astype(idx.dtype), starts, n_vertices, v_max,
            fill=fill, axis=axis, dedup=False,
        )
        return vals[inv.reshape(-1)]
    idxc = jnp.clip(idx, 0, n_vertices - 1)
    owner, slot = _owner_and_slot(idxc, starts, n_shards)
    local = (idxc - starts[owner]).astype(jnp.int32)
    req = jnp.full((n_shards, k), v_max, jnp.int32)
    req = req.at[owner, slot].set(local)
    _carried("gather_global", n_shards * k * (4 + _row_bytes(x)),
             k * (4 + _row_bytes(x)))
    with jax.named_scope("gather_global/request"):
        req_t = jax.lax.all_to_all(req, axis, split_axis=0, concat_axis=0)
    vals = gops.gather(x, req_t)  # [S, K, ...]; padded slots clip, unread
    with jax.named_scope("gather_global/reply"):
        vals_t = jax.lax.all_to_all(vals, axis, split_axis=0, concat_axis=0)
    out = vals_t[owner, slot]
    if fill is not None:
        import numpy as np

        fv = jnp.asarray(np.asarray(fill, np.dtype(x.dtype)).item(), x.dtype)
        oob = jnp.logical_or(idx < 0, idx >= n_vertices)
        oshape = oob.shape + (1,) * (out.ndim - oob.ndim)
        out = jnp.where(oob.reshape(oshape), fv, out)
    return out


def scatter_reduce(
    idx: jax.Array,  # i32[K] global target ids
    values: jax.Array,  # [K, ...] message payloads
    op: str,
    starts: jax.Array,  # i32[S+1]
    n_vertices: int,
    v_max: int,
    mask: Optional[jax.Array] = None,
    axis: str = AXIS,
) -> jax.Array:
    """Combine remote-write messages onto their owners → ``[v_max, ...]``.

    Returns each shard's *delta*: the combiner-fold of every message
    targeting its owned rows, identity where no message arrived. The caller
    folds the delta into the live field (receiver-side masking stays local
    to the owner). Out-of-range / masked targets are dropped, matching
    ``scatter_combine``'s ``mode="drop"``.
    """
    n_shards = starts.shape[0] - 1
    bool_io = values.dtype == jnp.bool_
    if bool_io:  # or/and combine via int min/max, as repro.graph.ops does
        values = values.astype(jnp.int32)
        op_eff = {"or": "max", "and": "min"}.get(op, op)
    else:
        op_eff = op
    ident = gops._identity_for(op_eff, values.dtype)
    padded = jnp.full((n_shards * v_max,) + values.shape[1:], ident)
    idxc = jnp.clip(idx, 0, n_vertices - 1)
    owner = _owner_of(idxc, starts, n_shards)
    pos = owner * v_max + (idxc - starts[owner])
    oob = jnp.logical_or(idx < 0, idx >= n_vertices)
    if mask is not None:
        oob = jnp.logical_or(oob, ~mask)
    pos = jnp.where(oob, n_shards * v_max, pos)  # out-of-range ⇒ dropped
    padded = gops.scatter_combine(padded, pos, values, op_eff)
    if n_shards == 1:
        out = padded
    else:
        _carried("scatter_reduce", padded.shape[0] * _row_bytes(padded),
                 idx.shape[0] * (4 + _row_bytes(values)))
        if op_eff == "sum":
            with jax.named_scope("scatter_reduce"):
                out = jax.lax.psum_scatter(
                    padded, axis, scatter_dimension=0, tiled=True
                )
        else:
            blocks = padded.reshape((n_shards, v_max) + padded.shape[1:])
            with jax.named_scope("scatter_reduce"):
                recv = jax.lax.all_to_all(
                    blocks, axis, split_axis=0, concat_axis=0
                )
            out = gops.combine_along_axis(op_eff, recv, axis=0)
    if bool_io:
        thresh = {"or": jnp.maximum(out, 0) > 0, "and": jnp.minimum(out, 1) > 0}
        return thresh[op] if op in thresh else out.astype(jnp.bool_)
    return out
