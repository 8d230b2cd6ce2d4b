"""Blocked-ELL segment-sum kernel (TPU Pallas).

This is the Pregel message combiner / GNN aggregation hot spot, adapted to
the TPU's strengths: instead of a scatter (serialized on TPU), the edges are
pre-bucketed so that all messages destined to segment block ``t`` live in
edge-slot range ``[t·budget, (t+1)·budget)``, and the kernel reduces each
bucket with **one-hot matmuls on the MXU**:

    out[t·nb : (t+1)·nb, :] = Σ_j onehot(local_dst_j)ᵀ @ vals_j

Grid: (T, budget/eb) with the edge dim innermost; a [nb, D] f32 VMEM scratch
accumulates partial sums across edge sub-blocks, written out once.

Padding slots carry local id = nb (one-hot row of zeros ⇒ no contribution).
VMEM per step: eb·D (vals) + eb (ids) + nb·eb (one-hot) + nb·D (scratch);
with eb=1024, nb=256, D=128, f32: ~2 MB. On the TPU the 1-D id block must
be a multiple of 1024, the tiling XLA gives a 1-D int32 array (T(1024));
interpret mode takes any ``eb``.

The one-hot matmul costs 2·eb·nb·D flops vs the scatter's eb·D — a
deliberate flops-for-regularity trade: on TPU the MXU delivers those flops
at peak while a scatter bottlenecks on serialized VREG updates. See
EXPERIMENTS.md §Perf for the roofline view.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: XLA's tile for a 1-D int32 array on the TPU; the id block must be a
#: multiple of it
ID_TILE = 1024


def _segsum_kernel(ids_ref, vals_ref, o_ref, acc_ref, *, nb, eb, n_e):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[...]  # [eb] local ids in [0, nb]; nb == padding
    vals = vals_ref[...]  # [eb, D]
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (nb, eb), 0) == ids[None, :]
    ).astype(vals.dtype)
    acc_ref[...] += jax.lax.dot_general(
        onehot, vals, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_e - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def segment_sum_ell_kernel(
    ids: jax.Array,  # [T * budget] local ids (dst - t*nb; padding = nb)
    vals: jax.Array,  # [T * budget, D] bucketed messages
    *,
    n_blocks: int,
    nb: int,
    budget: int,
    eb: int = 1024,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    d = vals.shape[1]
    eb = min(eb, budget)
    if budget % eb:
        raise ValueError(f"budget {budget} is not a multiple of eb={eb}")
    if not interpret and eb % ID_TILE:
        raise ValueError(
            f"eb={eb}: on the TPU the id block must be a multiple of "
            f"{ID_TILE} (1-D int32 tiling)"
        )
    n_e = budget // eb
    out_dtype = out_dtype or vals.dtype
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_segsum_kernel, nb=nb, eb=eb, n_e=n_e)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks, n_e),
        in_specs=[
            pl.BlockSpec((eb,), lambda t, j, _n=n_e: (t * _n + j,)),
            pl.BlockSpec((eb, d), lambda t, j, _n=n_e: (t * _n + j, 0)),
        ],
        out_specs=pl.BlockSpec((nb, d), lambda t, j: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * nb, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((nb, d), jnp.float32)],
        interpret=interpret,
    )(ids, vals)
