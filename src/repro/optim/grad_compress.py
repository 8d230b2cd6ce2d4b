"""int8 gradient compression with error feedback (DP all-reduce shrinker).

At multi-pod scale the gradient all-reduce crosses DCN; int8 quantization
cuts that traffic 4× (vs f32) / 2× (vs bf16). Error feedback accumulates
the quantization residual locally and re-injects it next step, which keeps
SGD/Adam convergence (Seide et al.; Karimireddy et al. — EF-SGD).

Two entry points:
* ``compress``/``decompress`` — per-tensor symmetric int8 with max-abs
  scale (pure functions; composable with any optimizer);
* ``make_compressed_dp_grad_fn`` — explicit-collective data-parallel
  gradient via ``shard_map``: per-shard grads → EF + quantize → int32
  ``psum`` (exact integer summation) → dequantize mean. This is the
  explicit alternative to GSPMD's implicit all-reduce when you want the
  wire format under your control.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def compress(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """g → (int8 q, f32 scale) with symmetric max-abs scaling."""
    g32 = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decompress(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compress_with_feedback(g, residual):
    """Error-feedback compression: returns (q, scale, new_residual)."""
    corrected = g.astype(jnp.float32) + residual
    q, scale = compress(corrected)
    new_residual = corrected - decompress(q, scale)
    return q, scale, new_residual


def make_compressed_dp_grad_fn(loss_fn, mesh, data_axis: str = "data"):
    """Data-parallel gradient with int8-over-the-wire all-reduce.

    Returns ``grad_fn(params, batch, residuals) -> (loss, grads, residuals)``
    where params are replicated, batch is sharded on ``data_axis``, and
    ``residuals`` is a params-shaped f32 pytree (init zeros).
    """

    def local(params, batch, residuals):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        n = jax.lax.psum(1, axis_name=data_axis)

        def reduce_leaf(gl, res):
            corrected = gl.astype(jnp.float32) + res
            # all shards must quantize against the SAME scale before the
            # integer sum — agree via a scalar pmax (negligible traffic)
            local_scale = jnp.maximum(jnp.max(jnp.abs(corrected)), 1e-30) / 127.0
            scale = jax.lax.pmax(local_scale, axis_name=data_axis)
            q = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(
                jnp.int8
            )
            new_res = corrected - q.astype(jnp.float32) * scale
            total = jax.lax.psum(q.astype(jnp.int32), axis_name=data_axis)
            mean = total.astype(jnp.float32) * scale / n
            return mean.astype(gl.dtype), new_res

        flat_g, treedef = jax.tree_util.tree_flatten(g)
        flat_r = treedef.flatten_up_to(residuals)
        out = [reduce_leaf(a, b) for a, b in zip(flat_g, flat_r)]
        grads = treedef.unflatten([o[0] for o in out])
        new_res = treedef.unflatten([o[1] for o in out])
        loss = jax.lax.pmean(loss, axis_name=data_axis)
        return loss, grads, new_res

    batch_spec = P(data_axis)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), batch_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
