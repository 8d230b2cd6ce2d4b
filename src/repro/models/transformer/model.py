"""Decoder-only LM: init, train loss, prefill, and decode-step.

Layers are stacked along a leading axis and executed with ``lax.scan``
(+ remat), keeping the HLO size O(1) in depth — essential for compiling
94-layer configs against 512 dry-run devices on one CPU.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.dist.sharding import BATCH, constrain
from repro.models import common
from repro.models.transformer import attention as attn_mod
from repro.models.transformer import moe as moe_mod
from repro.models.transformer.config import TransformerConfig


# ---------------------------------------------------------------------------
# parameters


def init_layer(key, cfg: TransformerConfig):
    d, hd = cfg.d_model, cfg.head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    s = 1.0 / math.sqrt(d)
    p: Dict[str, Any] = {
        "ln1": jnp.ones((d,), cfg.pdtype),
        "ln2": jnp.ones((d,), cfg.pdtype),
        "wq": common.dense_init(ks[0], d, h * hd, cfg.pdtype),
        "wk": common.dense_init(ks[1], d, hkv * hd, cfg.pdtype),
        "wv": common.dense_init(ks[2], d, hkv * hd, cfg.pdtype),
        "wo": common.dense_init(ks[3], h * hd, d, cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((hkv * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((hkv * hd,), cfg.pdtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.pdtype)
        p["k_norm"] = jnp.ones((hd,), cfg.pdtype)
    if cfg.moe is None:
        p["ffn"] = {
            "w1": common.dense_init(ks[4], d, cfg.d_ff, cfg.pdtype),
            "w3": common.dense_init(ks[5], d, cfg.d_ff, cfg.pdtype),
            "w2": common.dense_init(ks[6], cfg.d_ff, d, cfg.pdtype),
        }
    else:
        p["moe"] = moe_mod.init_moe_params(ks[7], d, cfg.moe, cfg.pdtype)
    return p


def init(key, cfg: TransformerConfig):
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    params = {
        "embed": (
            jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model)) * 0.02
        ).astype(cfg.pdtype),
        "layers": common.stack_init(
            k_layers, cfg.n_layers, lambda k: init_layer(k, cfg)
        ),
        "ln_f": jnp.ones((cfg.d_model,), cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (
            jax.random.normal(k_out, (cfg.vocab_size, cfg.d_model)) * 0.02
        ).astype(cfg.pdtype)
    return params


def abstract_params(cfg: TransformerConfig):
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# forward


def _attn_block(p, x, q_pos, k_pos, cfg, k_cache=None, v_cache=None, kv_mask=None):
    """Attention sub-block. If k_cache/v_cache given (decode), attends to the
    cache; returns (out, new_k, new_v) where new_k/new_v are this call's
    K/V (for cache update / prefill cache)."""
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = constrain(q.reshape(b, s, h, hd), (BATCH, None, "model", None))
    k = constrain(k.reshape(b, s, hkv, hd), (BATCH, None, "model", None))
    v = constrain(v.reshape(b, s, hkv, hd), (BATCH, None, "model", None))
    if cfg.qk_norm:
        q = common.rms_norm(q, p["q_norm"])
        k = common.rms_norm(k, p["k_norm"])
    q = attn_mod.apply_rope(q, q_pos, cfg.rope_theta)
    k = attn_mod.apply_rope(k, q_pos, cfg.rope_theta)
    new_k, new_v = k, v
    if k_cache is not None:
        k = jnp.concatenate([k_cache, k], axis=1)
        v = jnp.concatenate([v_cache, v], axis=1)
    out = attn_mod.attention(
        q, k, v, q_pos, k_pos, cfg, causal=True, kv_mask=kv_mask
    )
    return out.reshape(b, s, h * hd) @ p["wo"], new_k, new_v


def _ffn_block(p, x, cfg):
    b, s, d = x.shape
    if cfg.moe is None:
        f = p["ffn"]
        return common.swiglu(x, f["w1"], f["w3"], f["w2"]), 0.0
    y, aux = moe_mod.moe_ffn(x.reshape(b * s, d), p["moe"], cfg.moe)
    return y.reshape(b, s, d), aux


def forward(params, tokens: jax.Array, cfg: TransformerConfig):
    """Training/prefill-style full forward. Returns (hidden [B,S,D], aux)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    x = constrain(x, (BATCH, None, None))
    pos = jnp.arange(s, dtype=jnp.int32)

    def layer_fn(carry, lp):
        x, aux = carry
        # barrier: stops XLA LICM from hoisting the bf16→f32 upcast of the
        # carry out of the reverse loop (which would materialize an f32 copy
        # of the whole [L, B, S, D] remat stack — 2× activation memory)
        x = jax.lax.optimization_barrier(x)
        a, _, _ = _attn_block(lp, common.rms_norm(x, lp["ln1"]), pos, pos, cfg)
        x = constrain(x + a, (BATCH, None, None))
        f, aux_l = _ffn_block(lp, common.rms_norm(x, lp["ln2"]), cfg)
        # sequence-parallel layer boundary (Megatron SP): the remat-saved
        # carry is sharded on S over `model`, shrinking the [L,B,S,D] stack
        # 16×; GSPMD inserts the AG/RS pair around attention per layer.
        x = constrain(x + f, (BATCH, "model", None))
        return (x, aux + aux_l), None

    fn = jax.checkpoint(layer_fn) if cfg.remat else layer_fn
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.asarray(0.0, jnp.float32)),
                               params["layers"], unroll=cfg.scan_unroll)
    x = common.rms_norm(x, params["ln_f"])
    return x, aux


def logits_from_hidden(params, hidden, cfg):
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,vd->bsv", hidden, table)
    return constrain(logits, (BATCH, None, "model"))  # keep vocab sharded


def loss_fn(params, batch, cfg: TransformerConfig):
    """Next-token cross-entropy; batch = {tokens [B,S], labels [B,S]}."""
    hidden, aux = forward(params, batch["tokens"], cfg)
    logits = logits_from_hidden(params, hidden, cfg)
    ce = common.softmax_cross_entropy(logits, batch["labels"])
    return ce + 0.01 * aux


# ---------------------------------------------------------------------------
# serving: prefill + decode


def cache_len(cfg: TransformerConfig, seq_len: int) -> int:
    """SWA models only retain a window of KV (ring buffer at deploy time)."""
    if cfg.swa_window is not None:
        return min(seq_len, cfg.swa_window)
    return seq_len


def init_cache(cfg: TransformerConfig, batch: int, seq_len: int, dtype=None):
    dtype = dtype or cfg.cdtype
    c = cache_len(cfg, seq_len)
    shape = (cfg.n_layers, batch, c, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def decode_step(params, cache, tokens: jax.Array, cfg: TransformerConfig):
    """One decode step: tokens [B, 1] + cache → (logits [B, V], new cache).

    The cache is dense [L, B, C, Hkv, Dh]; `length` tracks the valid prefix.
    For SWA models C == window and positions wrap (ring buffer).
    """
    b = tokens.shape[0]
    c = cache["k"].shape[2]
    length = cache["length"]  # [B]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    q_pos = length[:, None]  # true position ids [B, 1]
    slot = length % c  # ring-buffer slot [B]
    # absolute position held by each cache slot: slot i holds position p with
    # p ≡ i (mod c) and length - c ≤ p < length (ring-buffer reconstruction)
    slots = jnp.arange(c, dtype=jnp.int32)[None]  # [1, C]
    base = length[:, None] - 1 - ((length[:, None] - 1 - slots) % c)
    k_pos = jnp.where(length[:, None] > 0, base, 0)
    kv_mask = (slots < length[:, None]) | (length[:, None] >= c)

    # the concatenated KV is [cache slots..., current token]
    k_pos_full = jnp.concatenate([k_pos, q_pos], axis=1)
    kv_mask_full = jnp.concatenate([kv_mask, jnp.ones((b, 1), jnp.bool_)], axis=1)

    def layer_fn(x, lp_and_cache):
        lp, kc, vc = lp_and_cache
        a, nk, nv = _attn_block(
            lp,
            common.rms_norm(x, lp["ln1"]),
            q_pos,
            k_pos_full,
            cfg,
            k_cache=kc,
            v_cache=vc,
            kv_mask=kv_mask_full,
        )
        x = x + a
        f, _ = _ffn_block(lp, common.rms_norm(x, lp["ln2"]), cfg)
        x = x + f
        # write new K/V into the ring slot
        bidx = jnp.arange(b)
        kc = kc.at[bidx, slot].set(nk[:, 0])
        vc = vc.at[bidx, slot].set(nv[:, 0])
        return x, (kc, vc)

    def scan_body(x, layer):
        lp, kc, vc = layer
        x, (kc, vc) = layer_fn(x, (lp, kc, vc))
        return x, (kc, vc)

    x, (new_k, new_v) = jax.lax.scan(
        scan_body, x, (params["layers"], cache["k"], cache["v"]),
        unroll=cfg.scan_unroll,
    )
    x = common.rms_norm(x, params["ln_f"])
    logits = logits_from_hidden(params, x, cfg)[:, 0]
    new_cache = {"k": new_k, "v": new_v, "length": length + 1}
    return logits, new_cache


def prefill(params, tokens: jax.Array, cfg: TransformerConfig,
            capacity: int = 0, full_logits: bool = True):
    """Full-sequence prefill: returns (logits, cache).

    ``capacity`` sets the KV ring-buffer size (0 ⇒ ``cache_len(cfg, s)``).
    The ring invariant is slot == position % capacity, so decode_step can
    reconstruct absolute positions for RoPE-consistent masking.
    ``full_logits=False`` (production serving) unembeds only the final
    position — a [B,S,V] logits tensor at 32k×152k vocab is ~20 GB/device
    and is never needed for sampling.
    """
    b, s = tokens.shape
    c = capacity or cache_len(cfg, s)
    pos = jnp.arange(s, dtype=jnp.int32)
    keep = min(s, c)
    kept_pos = jnp.arange(s - keep, s, dtype=jnp.int32)
    kept_slots = kept_pos % c

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)

    def layer_fn(x, lp):
        x = jax.lax.optimization_barrier(x)
        a, nk, nv = _attn_block(lp, common.rms_norm(x, lp["ln1"]), pos, pos, cfg)
        x = constrain(x + a, (BATCH, None, None))
        f, _ = _ffn_block(lp, common.rms_norm(x, lp["ln2"]), cfg)
        x = constrain(x + f, (BATCH, "model", None))
        # scatter the retained KVs into their ring slots; the stacked cache
        # shards its sequence dim over `model` (KV sequence parallelism)
        kc = jnp.zeros((b, c) + nk.shape[2:], nk.dtype)
        vc = jnp.zeros((b, c) + nv.shape[2:], nv.dtype)
        kc = kc.at[:, kept_slots].set(nk[:, s - keep:])
        vc = vc.at[:, kept_slots].set(nv[:, s - keep:])
        kc = constrain(kc, (BATCH, "model", None, None))
        vc = constrain(vc, (BATCH, "model", None, None))
        return x, (kc, vc)

    fn = jax.checkpoint(layer_fn) if cfg.remat else layer_fn
    x, (ks, vs) = jax.lax.scan(fn, x, params["layers"],
                               unroll=cfg.scan_unroll)
    x = common.rms_norm(x, params["ln_f"])
    if full_logits:
        logits = logits_from_hidden(params, x, cfg)
    else:
        last = constrain(x[:, -1:, :], (BATCH, None, None))
        logits = logits_from_hidden(params, last, cfg)[:, 0]
    cache = {
        "k": ks,
        "v": vs,
        "length": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


# ---------------------------------------------------------------------------
# dry-run input specs


def input_specs(cfg: TransformerConfig, shape: str, seq_len: int, batch: int):
    if shape == "train":
        return {
            "tokens": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32),
        }
    if shape == "prefill":
        return {"tokens": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)}
    if shape == "decode":
        c = cache_len(cfg, seq_len)
        return {
            "tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            "cache": {
                "k": jax.ShapeDtypeStruct(
                    (cfg.n_layers, batch, c, cfg.n_kv_heads, cfg.head_dim),
                    cfg.cdtype,
                ),
                "v": jax.ShapeDtypeStruct(
                    (cfg.n_layers, batch, c, cfg.n_kv_heads, cfg.head_dim),
                    cfg.cdtype,
                ),
                "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
            },
        }
    raise ValueError(shape)
