"""Wrapper: edge bucketing (blocked-ELL layout) + overflow fallback.

``segment_sum_ell(values, segment_ids, num_segments)``:

1. host/jnp preprocessing sorts edges by destination block and scatters them
   into per-block slot ranges of a fixed ``budget`` (rounded to the edge
   sub-block size). For power-law graphs the budget is set from the max
   block load; the overflow path (when a cap is given) falls back to
   ``jax.ops.segment_sum`` for the spilled edges and adds the two partial
   results — Pregel's combiner semantics make this trivially correct.
2. the Pallas kernel reduces each bucket with MXU one-hot matmuls.

The bucketing permutation is graph-structure-only, so in training it is
computed once per graph and reused every step (amortized to zero), exactly
like the CSR sort in any production GNN system.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce.kernel import segment_sum_ell_kernel


def build_ell_layout(
    segment_ids: jax.Array,
    num_segments: int,
    nb: int = 256,
    eb: int = 1024,
    budget_cap: Optional[int] = None,
):
    """Compute (slot permutation, budget, n_blocks) for the ELL layout.

    Returns ``slots[e]``: the flat slot index each edge lands in (or an
    out-of-range spill sentinel when ``budget_cap`` truncates), plus the
    layout dims. jnp-traceable, but intended to be computed once per graph.
    """
    n_blocks = -(-num_segments // nb)
    blk = segment_ids // nb  # [E]
    order = jnp.argsort(blk)
    sorted_blk = blk[order]
    counts = jnp.bincount(blk, length=n_blocks)
    budget = int(counts.max()) if not isinstance(counts, jax.core.Tracer) else 0
    # rank of each edge within its block
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    rank_sorted = jnp.arange(blk.shape[0]) - starts[sorted_blk]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    if budget_cap is not None:
        budget = min(budget, budget_cap) if budget else budget_cap
    budget = max(-(-budget // eb) * eb, eb)
    spill = rank >= budget
    slots = jnp.where(spill, n_blocks * budget, blk * budget + rank)
    return slots, int(budget), int(n_blocks), spill


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_segments", "nb", "eb", "budget", "n_blocks", "interpret",
    ),
)
def _run(values, segment_ids, slots, spill, num_segments, nb, eb, budget,
         n_blocks, interpret):
    d = values.shape[1]
    local = jnp.where(spill, nb, segment_ids % nb).astype(jnp.int32)
    ids_b = jnp.full((n_blocks * budget,), nb, jnp.int32)
    vals_b = jnp.zeros((n_blocks * budget, d), values.dtype)
    ids_b = ids_b.at[slots].set(local, mode="drop")
    vals_b = vals_b.at[slots].set(values, mode="drop")
    out = segment_sum_ell_kernel(
        ids_b, vals_b, n_blocks=n_blocks, nb=nb, budget=budget, eb=eb,
        out_dtype=values.dtype, interpret=interpret,
    )[:num_segments]
    # spilled edges (over-budget) go through the XLA combiner and merge in —
    # Pregel's accumulative-write semantics make the split trivially correct
    spilled_vals = jnp.where(spill[:, None], values, 0)
    out = out + jax.ops.segment_sum(
        spilled_vals, segment_ids, num_segments=num_segments
    )
    return out


def segment_sum_ell(
    values: jax.Array,  # [E, D]
    segment_ids: jax.Array,  # [E]
    num_segments: int,
    mask: Optional[jax.Array] = None,
    nb: int = 256,
    eb: int = 1024,
    budget_cap: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in replacement for masked segment-sum on TPU."""
    if mask is not None:
        segment_ids = jnp.where(mask, segment_ids, num_segments)
        values = jnp.where(mask[:, None], values, 0)
    # route padding/masked edges to a ghost block, then slice it away
    n_seg_pad = num_segments + 1
    slots, budget, n_blocks, spill = build_ell_layout(
        segment_ids, n_seg_pad, nb=nb, eb=eb, budget_cap=budget_cap
    )
    out = _run(
        values, segment_ids, slots, spill, n_seg_pad, nb, eb, budget,
        n_blocks, interpret,
    )
    return out[:num_segments]
