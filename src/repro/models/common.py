"""Shared NN building blocks (pure-functional, no framework).

Parameters are nested dicts of jnp arrays. Initializers take an explicit key
and return the pytree; ``abstract`` variants return ShapeDtypeStructs so the
multi-pod dry-run never allocates memory.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * gamma


def swiglu(x, w1, w3, w2):
    """SwiGLU FFN: (silu(x@w1) * (x@w3)) @ w2."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


def gelu_mlp(x, w1, b1, w2, b2):
    return jax.nn.gelu(x @ w1 + b1) @ w2 + b2


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Token-mean CE; logits [..., V] (accumulated in fp32), labels [...].

    Written gather-free (iota+select instead of take_along_axis) so GSPMD
    keeps the vocab dimension sharded — a vocab gather would all-gather
    [B,S,V] logits per device.
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = (
        jnp.sum(jnp.where(iota == labels[..., None], shifted, 0.0), axis=-1)
        + m[..., 0]
    )
    return jnp.mean(lse - gold)


def sigmoid_bce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def scan_layers(layer_fn, params_stacked, x, *, remat: bool = True, unroll: int = 1):
    """Run ``layer_fn(layer_params, x) -> x`` over a layer-stacked param
    pytree with ``lax.scan`` (+ optional remat for O(1)-layers memory)."""

    fn = jax.checkpoint(layer_fn) if remat else layer_fn

    def body(carry, layer_params):
        return fn(layer_params, carry), None

    out, _ = jax.lax.scan(body, x, params_stacked, unroll=unroll)
    return out


def stack_init(key, n: int, init_fn):
    """Initialize ``n`` layers and stack leaves along axis 0."""
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def abstract_like(init_fn, *args, **kwargs):
    """ShapeDtypeStruct pytree of an initializer without running it."""
    return jax.eval_shape(init_fn, *args, **kwargs)


def count_params(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(
        int(np.prod(leaf.shape)) if leaf.shape else 1 for leaf in leaves
    )


import numpy as np  # noqa: E402  (used by count_params only)
