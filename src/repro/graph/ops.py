"""Segment/gather/scatter primitives — the message-passing substrate.

These wrap ``jax.ops.segment_*`` and indexed updates with the combiner
semantics Palgol requires (accumulative-only remote writes). Out-of-range
indices (the padding sentinel) are *dropped*, matching Pregel's "no message"
semantics.

JAX has no native EmbeddingBag / CSR sparse; per the assignment, message
passing over an edge-index → node scatter IS part of the system and lives
here. Where the edges are sorted by segment and each segment's run end is
known, an order-independent reduction needs no scatter:
:func:`sorted_segment_reduce` scans the runs and reads each at its end.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# identity element per combiner, keyed by op name
COMBINE_IDENTITY = {
    "sum": 0.0,
    "min": jnp.inf,
    "max": -jnp.inf,
    "prod": 1.0,
    "and": True,
    "or": False,
}


def _identity_for(op: str, dtype) -> jax.Array:
    ident = COMBINE_IDENTITY[op]
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        ident = {"sum": 0, "min": info.max, "max": info.min, "prod": 1}[op]
    if dtype == jnp.bool_:
        ident = {"and": True, "or": False, "sum": False, "max": False, "min": True}[op]
    return jnp.asarray(ident, dtype=dtype)


#: elementwise combiner application — the single source for every site that
#: folds two already-reduced values (remote-write deltas, cross-shard
#: partials); keep in sync with COMBINE_IDENTITY above
COMBINE_FN = {
    "sum": jnp.add,
    "prod": jnp.multiply,
    "min": jnp.minimum,
    "max": jnp.maximum,
    "or": jnp.logical_or,
    "and": jnp.logical_and,
}


def combine(op: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise ``a op b`` for a Palgol combiner."""
    if op not in COMBINE_FN:
        raise ValueError(f"unknown combiner {op!r}")
    return COMBINE_FN[op](a, b)


def combine_along_axis(op: str, arr: jax.Array, axis: int) -> jax.Array:
    """Reduce one array axis with a Palgol combiner."""
    reducers = {
        "sum": jnp.sum,
        "prod": jnp.prod,
        "min": jnp.min,
        "max": jnp.max,
        "or": jnp.any,
        "and": jnp.all,
    }
    if op not in reducers:
        raise ValueError(f"unknown combiner {op!r}")
    return reducers[op](arr, axis=axis)


def segment_reduce(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    op: str = "sum",
    indices_are_sorted: bool = False,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Reduce ``values`` by ``segment_ids`` with combiner ``op``.

    Unreduced segments receive the combiner identity (matching Palgol's list
    comprehension over an empty neighbor list, e.g. ``minimum [] = inf``).
    """
    if mask is not None:
        ident = _identity_for(op, values.dtype)
        mshape = mask.shape + (1,) * (values.ndim - mask.ndim)
        values = jnp.where(mask.reshape(mshape), values, ident)
    kwargs = dict(
        num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )
    if op == "sum":
        return jax.ops.segment_sum(values, segment_ids, **kwargs)
    if op == "prod":
        return jax.ops.segment_prod(values, segment_ids, **kwargs)
    if op == "min":
        out = jax.ops.segment_min(values, segment_ids, **kwargs)
        # segment_min fills empty segments with +max of dtype already; but for
        # float we want +inf explicitly
        return out
    if op == "max":
        return jax.ops.segment_max(values, segment_ids, **kwargs)
    if op == "or":
        asint = jax.ops.segment_max(values.astype(jnp.int32), segment_ids, **kwargs)
        # empty segments reduce to INT_MIN; identity of `or` is False
        return jnp.maximum(asint, 0).astype(jnp.bool_)
    if op == "and":
        asint = jax.ops.segment_min(values.astype(jnp.int32), segment_ids, **kwargs)
        # empty segments reduce to INT_MAX; identity of `and` is True
        return jnp.minimum(asint, 1).astype(jnp.bool_)
    raise ValueError(f"unknown combiner {op!r}")


def is_order_independent(op: str, dtype) -> bool:
    """Whether combiner ``op`` over ``dtype`` gives the same bits in any
    order of its values: ``min``, ``max``, ``and``, ``or`` and integer
    ``sum``. A float ``sum`` or ``prod`` rounds differently in another
    order, so only the scatter reproduces it."""
    if op in ("min", "max", "and", "or"):
        return True
    return op == "sum" and jnp.issubdtype(dtype, jnp.integer)


#: slots per row of the segmented scan: one row of lanes
_SCAN_ROW = 128


def _shift_right(x: jax.Array, k: int, fill) -> jax.Array:
    """``x`` moved ``k`` places along axis 1, ``fill`` in the first ``k``."""
    widths = [(0, 0)] * x.ndim
    widths[1] = (k, 0)
    return jnp.pad(x[:, :-k], widths, constant_values=fill)


def _segmented_scan(x, seg, fn, ident) -> jax.Array:
    """Inclusive scan of ``x`` with ``fn`` along axis 0 within each run of
    equal ``seg`` (ascending, so a run is contiguous).

    The slots are viewed as rows of :data:`_SCAN_ROW`, and every step
    below is a Hillis-Steele step: after the step of shift ``k`` a slot
    holds its run's values over the last ``2k`` slots, as a slot ``k``
    back belongs to the same run exactly when its ``seg`` is equal. Each
    row is scanned in log2 of its width steps of static shifts; the rows'
    last slots are then scanned in a loop of steps over the rows, and each
    row folds in the row before's result where its leading run continues
    that row's last run. The loop keeps the trace's structure the same for
    any number of rows.
    """
    e = x.shape[0]
    width = _SCAN_ROW
    rows = -(-e // width)
    pad = rows * width - e
    if pad:  # the padding continues the last run with the identity
        x = jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], ident, x.dtype)])
        seg = jnp.concatenate([seg, jnp.broadcast_to(seg[-1:], (pad,))])
    trailing = (1,) * (x.ndim - 1)  # seg broadcast over the value's dims
    xs = x.reshape((rows, width) + x.shape[1:])
    ss = seg.reshape((rows, width) + trailing)
    k = 1
    while k < width:
        same = ss == _shift_right(ss, k, -1)
        xs = jnp.where(same, fn(xs, _shift_right(xs, k, ident)), xs)
        k *= 2
    if rows > 1:
        # each row's last slot, as reductions of the rows: its value is the
        # row's only one that is not the identity, its seg the row's largest
        last = (jnp.arange(width) == width - 1).reshape((1, width) + trailing)
        tail = jax.lax.reduce(jnp.where(last, xs, ident), ident, fn, (1,))
        tail_seg = jnp.max(ss, axis=1)
        row = jnp.arange(rows).reshape((rows,) + trailing)

        def step(i, acc):
            k = jnp.left_shift(1, i)
            same = (row >= k) & (jnp.roll(tail_seg, k, axis=0) == tail_seg)
            return jnp.where(same, fn(acc, jnp.roll(acc, k, axis=0)), acc)

        tails = jax.lax.fori_loop(0, (rows - 1).bit_length(), step, tail)
        before = jnp.concatenate(
            [jnp.full((1,) + tails.shape[1:], ident, tails.dtype), tails[:-1]]
        )
        before_seg = jnp.concatenate(
            [jnp.full((1,) + trailing, -1, ss.dtype), tail_seg[:-1]]
        )
        cont = ss == before_seg[:, None]
        xs = jnp.where(cont, fn(xs, before[:, None]), xs)
    return xs.reshape((rows * width,) + x.shape[1:])[:e]


def sorted_segment_reduce(
    values: jax.Array,
    segment_ids: jax.Array,
    ends: jax.Array,
    op: str,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Reduce ``values`` over runs of ascending ``segment_ids``, without a
    scatter: :func:`segment_reduce` with ``num_segments = len(ends)`` for
    an order-independent ``op`` (:func:`is_order_independent`), to the bit.

    ``ends[v]`` is one past the last slot of segment ``v`` (``searchsorted``
    of ``segment_ids`` on the right); ids at or past ``len(ends)``, such as
    the padding sentinel, sort last and are read by no segment. A segmented
    inclusive scan runs along the slots, and each segment reads it at its
    run's last slot; a segment with no slot gets the combiner's identity.
    """
    if not is_order_independent(op, values.dtype):
        raise ValueError(f"{op!r} over {values.dtype} depends on the order")
    ident = _identity_for(op, values.dtype)
    if values.shape[0] == 0:
        return jnp.full(ends.shape + values.shape[1:], ident)
    if mask is not None:
        mshape = mask.shape + (1,) * (values.ndim - mask.ndim)
        values = jnp.where(mask.reshape(mshape), values, ident)
    scan = _segmented_scan(values, segment_ids, COMBINE_FN[op], ident)
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    last = jnp.take(scan, jnp.maximum(ends - 1, 0), axis=0, mode="clip")
    nonempty = (ends > starts).reshape(ends.shape + (1,) * (values.ndim - 1))
    return jnp.where(nonempty, last, ident)


def gather(field: jax.Array, idx: jax.Array, fill=None) -> jax.Array:
    """``field[idx]`` with out-of-range indices reading a fill value.

    This is the dense-runtime realization of a Palgol remote *read*: on a
    sharded field, XLA lowers it to the gather collective schedule chosen by
    the partitioner. The padding sentinel (== n_vertices) reads ``fill``.
    """
    if fill is None:
        return jnp.take(field, idx, axis=0, mode="clip")
    # fill_value must be a static (hashable) scalar, not a traced array
    import numpy as np

    fill_scalar = np.asarray(fill, np.dtype(field.dtype)).item()
    return jnp.take(field, idx, axis=0, mode="fill", fill_value=fill_scalar)


def scatter_combine(
    buffer: jax.Array,
    idx: jax.Array,
    values: jax.Array,
    op: str = "sum",
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Apply accumulative remote writes: ``buffer[idx] op= values``.

    Out-of-range indices are dropped (``mode="drop"``), which both implements
    Pregel's "message to nobody" for padding rows and makes halted-vertex
    masking cheap (redirect idx to the sentinel).
    """
    if mask is not None:
        idx = jnp.where(mask, idx, buffer.shape[0])  # out-of-range => dropped
    at = buffer.at[idx]
    if op == "sum":
        return at.add(values, mode="drop")
    if op == "min":
        return at.min(values, mode="drop")
    if op == "max":
        return at.max(values, mode="drop")
    if op == "prod":
        return at.mul(values, mode="drop")
    if op == "or":
        return (
            buffer.astype(jnp.int32)
            .at[idx]
            .max(values.astype(jnp.int32), mode="drop")
            .astype(buffer.dtype)
        )
    if op == "and":
        return (
            buffer.astype(jnp.int32)
            .at[idx]
            .min(values.astype(jnp.int32), mode="drop")
            .astype(buffer.dtype)
        )
    raise ValueError(f"unknown combiner {op!r}")


def in_degrees(graph) -> jax.Array:
    ones = graph.edge_mask.astype(jnp.int32)
    return jax.ops.segment_sum(
        ones, graph.dst, num_segments=graph.n_vertices, indices_are_sorted=True
    )


def out_degrees(graph) -> jax.Array:
    ones = graph.t_mask.astype(jnp.int32)
    return jax.ops.segment_sum(
        ones, graph.t_src, num_segments=graph.n_vertices, indices_are_sorted=True
    )
