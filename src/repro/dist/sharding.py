"""Sharding rules and the active-mesh context — the distribution layer.

This is the single place where logical parallelism decisions live; models
never name mesh axes directly for *data* parallelism, they tag dimensions
with the logical axes below and the rules here map them onto whatever mesh
is active (or no-op entirely when none is — smoke tests, oracles, CPU CI).

Physical mesh axes (see ``repro.launch.mesh``):

* ``pod``    — inter-pod data parallelism (multi-pod production mesh only);
* ``data``   — intra-pod data parallelism / FSDP shard axis;
* ``model``  — tensor/expert parallelism.

Logical axes:

* :data:`BATCH` — the data-parallel group (``pod`` × ``data``): batch dims
  of activations, token streams, KV caches;
* :data:`ALL`   — every mesh axis flattened: the edge/node dimension of
  graph workloads, where the mesh is one big 1-D partition (vertex-cut with
  replicated vertex state — see ``repro.graph.ops``).

Every spec derivation routes through :func:`_maybe`, which drops a mesh
axis from a dimension that it does not evenly divide (GSPMD would reject
the constraint; padding to divisibility is the caller's optimization, not a
correctness requirement).

Param-spec policy (``lm_param_spec``, keyed by param path):

=====================  ======================  ===========================
path                   shape                   spec (fsdp mode)
=====================  ======================  ===========================
``embed``/``unembed``  ``[V, D]``              ``P("model", "data")``
``layers/wq|wk|wv``    ``[L, D, H·hd]``        ``P(None, "data", "model")``
``layers/wo``          ``[L, H·hd, D]``        ``P(None, "model", "data")``
``layers/ffn/w1|w3``   ``[L, D, F]``           ``P(None, "data", "model")``
``layers/ffn/w2``      ``[L, F, D]``           ``P(None, "model", "data")``
``layers/moe/w*``      ``[L, E, D, F]``        ``P(None, "model", "data", None)``
``layers/moe/router``  ``[L, D, E]``           ``P()``  (fp32, tiny — keep
                                               routing bit-identical)
norms / biases         ``[L, D]`` / ``[D]``    ``P()``
=====================  ======================  ===========================

i.e. the *parallel* matmul dim (heads / ffn / experts) shards over
``model`` and the reduction dim shards over ``data`` (FSDP); ``zero1``
mode keeps only the ``model`` shards on the stored params (the optimizer
state keeps the full 2-D sharding — pass ``mode="fsdp"`` for it).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# --------------------------------------------------------------------------
# logical axes

ALL = "__all__"  #: every mesh axis, flattened (graph edge/node dims)
BATCH = "__batch__"  #: the data-parallel group (pod × data)
SHARD = "shard"  #: the 1-D vertex-partition axis (``repro.graph.partition``)

#: physical axes belonging to the data-parallel group, in mesh order
_DATA_AXES = ("pod", "data")
#: every physical axis this layer knows about, in mesh order
_MESH_AXES = ("pod", "data", "model")

_AxisEntry = Union[None, str, Tuple[str, ...]]


# --------------------------------------------------------------------------
# active mesh context

_ACTIVE_MESH: Optional[Mesh] = None


def activate(mesh: Mesh) -> Mesh:
    """Make ``mesh`` the process-wide active mesh.

    ``constrain`` (and the mesh-aware dispatch in ``repro.graph.ops`` /
    ``repro.models.transformer.moe``) consult this; with no active mesh
    they all degrade to their single-device reference paths.
    """
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    return mesh


def deactivate() -> None:
    """Clear the active mesh (idempotent)."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = None


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


# --------------------------------------------------------------------------
# axis resolution helpers


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Physical axes of the data-parallel group present on ``mesh``."""
    return tuple(a for a in _DATA_AXES if a in mesh.shape)


def all_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Every known physical axis present on ``mesh``, in mesh order."""
    return tuple(a for a in _MESH_AXES if a in mesh.shape)


def _collapse(entry: Sequence[str]) -> _AxisEntry:
    """() → None, (a,) → a, (a, b, ...) → tuple (PartitionSpec idiom)."""
    entry = tuple(entry)
    if not entry:
        return None
    if len(entry) == 1:
        return entry[0]
    return entry


def _resolve(axes: Sequence[Any], mesh: Mesh) -> Tuple[_AxisEntry, ...]:
    """Map logical entries (ALL / BATCH) to physical axis entries."""
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        elif a == ALL:
            out.append(_collapse(all_axes(mesh)))
        elif a == BATCH:
            out.append(_collapse(data_axes(mesh)))
        else:
            out.append(a if isinstance(a, tuple) else str(a))
    return tuple(out)


def axis_size(entry: _AxisEntry, mesh: Mesh) -> int:
    """Product of mesh-axis sizes named by ``entry`` (1 for ``None``)."""
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for name in names:
        n *= mesh.shape[name]
    return n


def _maybe(
    axes: Sequence[_AxisEntry], shape: Sequence[int], mesh: Mesh
) -> P:
    """PartitionSpec over ``axes``, dropping entries that cannot apply.

    An entry is kept only if every named axis exists on ``mesh`` and the
    product of their sizes evenly divides the corresponding dimension;
    otherwise that dimension falls back to replication. Entries beyond
    ``len(shape)`` are truncated (a spec longer than the array rank is
    rejected by ``with_sharding_constraint``). This is what makes every
    rule in this module total: an indivisible (arch, mesh) pair degrades
    gracefully instead of failing to lower.
    """
    out = []
    for i, entry in enumerate(axes[: len(shape)]):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        if any(name not in mesh.shape for name in names):
            out.append(None)
            continue
        if shape[i] % axis_size(entry, mesh) != 0:
            out.append(None)
            continue
        out.append(entry)
    return P(*out)


def constrain(x: jax.Array, axes: Sequence[Any]) -> jax.Array:
    """``with_sharding_constraint`` against the active mesh; no-op without.

    ``axes`` is one entry per dimension: ``None`` (replicated), a physical
    axis name, a tuple of names, or a logical axis (:data:`ALL`,
    :data:`BATCH`). Indivisible entries are dropped per :func:`_maybe`, so
    ``constrain`` is always safe to call on oddly-shaped values.
    """
    mesh = _ACTIVE_MESH
    if mesh is None:
        return x
    spec = _maybe(_resolve(axes, mesh), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# parameter sharding rules (path-keyed)

#: param names that are always replicated (norm gains, biases, scalars)
_REPLICATED_NAMES = frozenset(
    {"ln1", "ln2", "ln_f", "q_norm", "k_norm", "bq", "bk", "bv", "b",
     "router", "step"}
)
#: column-parallel matmuls: reduction dim → data (FSDP), output dim → model
_COL_PARALLEL = frozenset({"wq", "wk", "wv", "w1", "w3"})
#: row-parallel matmuls: input dim → model, output dim → data (FSDP)
_ROW_PARALLEL = frozenset({"wo", "w2"})


def _drop_data(spec: P) -> P:
    """zero1 mode: strip the data-group axes (params stay model-sharded)."""

    def strip(entry):
        if entry is None:
            return None
        names = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(n for n in names if n not in _DATA_AXES)
        return _collapse(kept)

    return P(*(strip(e) for e in spec))


def lm_param_spec(path: str, leaf, mesh: Mesh, mode: str = "fsdp") -> P:
    """Sharding spec for one LM param, keyed by its ``/``-joined path.

    ``leaf`` needs only ``.shape`` (arrays and ShapeDtypeStructs both
    work). See the module docstring for the policy table.
    """
    if mode not in ("fsdp", "zero1"):
        raise ValueError(f"unknown param mode {mode!r}")
    shape = leaf.shape
    name = path.rsplit("/", 1)[-1]
    dat = _collapse(data_axes(mesh))

    if name in _REPLICATED_NAMES or len(shape) <= 1:
        return P()
    if name in ("embed", "unembed"):
        spec = _maybe(("model", dat), shape, mesh)
    elif "moe" in path.split("/") and name in ("w1", "w2", "w3") and len(shape) >= 4:
        # stacked expert weights [L, E, D, F]: experts → model (EP), the
        # next dim → data (FSDP). Same pattern for w2 [L, E, F, D].
        lead = (None,) * (len(shape) - 3)
        spec = _maybe(lead + ("model", dat, None), shape, mesh)
    elif name in _COL_PARALLEL:
        lead = (None,) * (len(shape) - 2)
        spec = _maybe(lead + (dat, "model"), shape, mesh)
    elif name in _ROW_PARALLEL:
        lead = (None,) * (len(shape) - 2)
        spec = _maybe(lead + ("model", dat), shape, mesh)
    else:
        return P()
    if mode == "zero1":
        spec = _drop_data(spec)
    return spec


def gnn_param_spec(path: str, leaf, mesh: Mesh, mode: str = "fsdp") -> P:
    """GNN params are small relative to node/edge state — replicate.

    The parallelism of the graph families lives entirely in the activation
    sharding (:data:`ALL` on node/edge dims) and the shard_map message
    passing; replicated params make every matmul local.
    """
    del path, leaf, mesh, mode
    return P()


def recsys_param_spec(path: str, leaf, mesh: Mesh, mode: str = "fsdp") -> P:
    """RecSys: shard the (huge) embedding tables on vocab, replicate MLP."""
    del mode
    shape = leaf.shape
    name = path.rsplit("/", 1)[-1]
    if "embed" in name and len(shape) >= 2:
        # [n_fields, V, D] (or [V, D]): vocab rows across the whole mesh
        lead = (None,) * (len(shape) - 2)
        return _maybe(lead + (_collapse(all_axes(mesh)), None), shape, mesh)
    return P()


_PARAM_RULES = {
    "lm": lm_param_spec,
    "gnn": gnn_param_spec,
    "recsys": recsys_param_spec,
}


def _path_str(key_path) -> str:
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:  # pragma: no cover - exotic pytree nodes
            parts.append(str(k))
    return "/".join(parts)


def param_shardings(kind: str, params, mesh: Mesh, mode: str = "fsdp"):
    """Pytree of ``NamedSharding`` matching ``params``, per-family rules.

    ``kind`` ∈ {"lm", "gnn", "recsys"}; ``mode`` ∈ {"fsdp", "zero1"}
    (zero1 is meaningful for "lm" only — stored params keep just their
    ``model`` shards while the optimizer state, requested separately with
    ``mode="fsdp"``, stays fully 2-D sharded).
    """
    rule = _PARAM_RULES[kind]
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh, rule(_path_str(kp), leaf, mesh, mode=mode)
        ),
        params,
    )


# --------------------------------------------------------------------------
# batch / activation shardings


def lm_batch_spec(mesh: Mesh, batch: int) -> P:
    """Spec for a ``[B, ...]`` token-stream array: batch over the DP group."""
    return _maybe((_collapse(data_axes(mesh)),), (batch,), mesh)


def lm_cache_spec(mesh: Mesh, cfg, batch: int, cache: int) -> P:
    """Spec for the stacked KV cache ``[L, B, C, Hkv, hd]``.

    Batch shards over the DP group and the cache *sequence* dim over
    ``model`` (KV sequence parallelism — ``n_kv_heads`` is routinely
    smaller than the model axis, the window length never is), matching the
    per-layer ``constrain`` in ``transformer.model.prefill``.
    """
    shape = (cfg.n_layers, batch, cache, cfg.n_kv_heads, cfg.head_dim)
    return _maybe(
        (None, _collapse(data_axes(mesh)), "model", None, None), shape, mesh
    )


def batch_shardings(kind: str, batch_specs, mesh: Mesh):
    """Pytree of ``NamedSharding`` for model inputs.

    * ``"lm"``: leading (batch) dim over the data-parallel group;
    * ``"gnn"`` / ``"recsys"``: leading (node/edge/batch) dim over *every*
      mesh axis — graph/recsys state is 1-D partitioned across the
      flattened mesh, matching :data:`ALL` constraints in the models.
    """
    entries = {
        "lm": _collapse(data_axes(mesh)),
        "gnn": _collapse(all_axes(mesh)),
        "recsys": _collapse(all_axes(mesh)),
    }
    if kind not in entries:
        raise ValueError(
            f"unknown batch kind {kind!r}; expected one of {sorted(entries)}"
        )
    entry = entries[kind]

    def leaf_sharding(leaf):
        if not getattr(leaf, "shape", ()):  # scalars
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _maybe((entry,), leaf.shape, mesh))

    return jax.tree_util.tree_map(leaf_sharding, batch_specs)


def replicated(x, mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (optimizer step counters, scalars)."""
    del x
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------
# vertex-partition shardings (repro.graph.partition)


def shard_mesh(n_shards: Optional[int] = None, devices=None) -> Mesh:
    """1-D ``("shard",)`` mesh for partitioned vertex state.

    The partitioned Pregel engine flattens whatever devices it is given
    into one shard axis — one contiguous vertex range per device. Defaults
    to every local device; pass ``n_shards`` to use a prefix of them.
    """
    import numpy as np

    devs = list(jax.devices()) if devices is None else list(devices)
    if n_shards is not None:
        if n_shards > len(devs):
            raise ValueError(
                f"n_shards={n_shards} exceeds available devices ({len(devs)})"
            )
        devs = devs[:n_shards]
    return Mesh(np.array(devs), (SHARD,))


def vertex_partition_spec(ndim: int = 2) -> P:
    """Spec for a ``[S, ...]`` per-shard block array: leading dim over
    :data:`SHARD`, everything else replicated."""
    return P(SHARD, *(None,) * (ndim - 1))


def vertex_partition_shardings(tree, mesh: Mesh):
    """Pytree of ``NamedSharding`` for partitioned per-shard arrays.

    Leading dims that the shard axis divides evenly (the ``[S, ...]``
    blocks of a ``PartitionedGraph`` and of partitioned fields) shard over
    :data:`SHARD`; everything else — the ``[S+1]`` owner map, scalars —
    replicates, per the :func:`_maybe` totality rule.
    """

    def leaf_sharding(leaf):
        shape = getattr(leaf, "shape", ())
        if not shape:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _maybe((SHARD,), shape, mesh))

    return jax.tree_util.tree_map(leaf_sharding, tree)
