"""Parameter / batch / cache sharding rules for the production meshes.

Megatron-style tensor parallelism by leaf name:

* column-parallel (output dim over "model"): ``wq wk wv w_in w_gate`` and
  every other ≥2-D multiplicative weight by default — the *last* axis;
* row-parallel (input dim over "model"): ``wo w_out out_proj_w`` — the
  second-to-last axis, so the TP pair (col-parallel up, row-parallel down)
  needs a single all-reduce per block;
* ``embed_tok`` shards the vocab axis, ``head_w`` the vocab (last) axis.

Divisibility is validated per leaf: a dim that does not divide the mesh
axis size **drops** that axis (replicates) instead of erroring — e.g. an
odd vocab like 50281 on a 4-way model axis.  This is the rule
``tests/test_dist.py::test_param_sharding_rules_divisibility`` pins down.

``zero=True`` additionally shards a remaining axis over the data axes
(ZeRO-style param partitioning, for the archs that do not fit replicated);
``zero_cols=True`` shards the matmul dim *orthogonal* to the model axis
over the data axes (the "tp_zcols" dry-run policy).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# Leaves whose *input* dim is model-sharded (row-parallel in Megatron terms).
ROW_PARALLEL = ("wo", "w_out", "out_proj_w")
# Embedding-style leaves: shard the vocab/first axis.
VOCAB_FIRST = ("embed_tok",)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The batch-sharding (pure data parallel) axes present in this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _leaf_name(path) -> str:
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            return key
    return ""


def _model_dim(name: str, ndim: int) -> Optional[int]:
    """Which dim the model axis shards for this leaf (None: replicate)."""
    if name.endswith("_cb"):
        return None             # codebooks are tiny: replicate
    if name.endswith("_pidx"):
        name = name[:-5]        # bit-packed indices shard like their weight
    elif name.endswith("_idx"):
        name = name[:-4]        # quantized leaves shard like their weight
    if ndim < 2:
        return None
    if name in VOCAB_FIRST:
        return 0
    if name in ROW_PARALLEL:
        return ndim - 2
    return ndim - 1


def param_shardings(params: PyTree, mesh: Mesh, zero: bool = False,
                    zero_cols: bool = False) -> PyTree:
    """NamedSharding tree congruent with ``params`` (arrays or
    ShapeDtypeStructs — only ``.shape`` is read)."""
    daxes = batch_axes(mesh)
    model = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1
    dsize = _axis_size(mesh, daxes)

    def rule(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        parts: list = [None] * len(shape)
        mdim = _model_dim(name, len(shape))
        if mdim is not None and model > 1 and shape[mdim] % model == 0:
            parts[mdim] = "model"
        else:
            mdim = None
        if zero_cols and mdim is not None and dsize > 1:
            # rows over data, cols over model (or vice versa for row-par)
            other = len(shape) - 1 if mdim != len(shape) - 1 else len(shape) - 2
            if parts[other] is None and shape[other] % dsize == 0:
                parts[other] = daxes
        elif zero and dsize > 1 and len(shape) >= 2:
            for d in range(len(shape)):
                if parts[d] is None and shape[d] % dsize == 0:
                    parts[d] = daxes
                    break
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(rule, params)


def batch_shardings(batch: PyTree, mesh: Mesh) -> PyTree:
    """Shard the leading (global-batch) dim over the data axes."""
    daxes = batch_axes(mesh)
    dsize = _axis_size(mesh, daxes)

    def rule(leaf):
        if leaf.ndim == 0 or dsize <= 1 or leaf.shape[0] % dsize:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(daxes, *([None] * (leaf.ndim - 1))))

    return jax.tree_util.tree_map(rule, batch)


def engine_cache_shardings(caches: PyTree, mesh: Mesh, *, n_slots: int,
                           n_pages: Optional[int] = None,
                           n_kv: Optional[int] = None) -> PyTree:
    """Shardings for the engine's paged/slot caches (``init_paged_cache``).

    Told apart by the cache's type, then by the second axis:

    * dense KV pools (``PagedKVCache``, lane-dense ``[G, n_pages + 1,
      page, KV·hd]``) — the page axis **replicates** over the data axes:
      any slot's page-table entry may point at any physical page, so
      pages cannot be partitioned by batch.  The lanes split over
      ``model`` by whole kv heads (the head's pages live with its
      projection shard) when ``n_kv`` divides the axis;
    * other page pools (``[G, n_pages + 1, page, ...]``) replicate their
      pages too; the kv-head axis of 5-D quantized word pools shards over
      ``model``; MLA latent pools (4-D) replicate;
    * per-slot state (``shape[1] == n_slots``: SSM/RG-LRU state, conv
      tails, sliding-window ring buffers) — the slot axis shards over
      the data axes exactly like a decode batch, and 5-D KV-style leaves
      keep their kv-head axis on ``model``.

    Pass ``n_pages`` so the pool check wins when ``n_pages + 1 ==
    n_slots`` (an oversubscribed pool could otherwise be mistaken for
    slot state and have its pages data-sharded; replication is the
    always-correct fallback), and ``n_kv`` (the model's kv heads) so
    dense pools can split their lanes.
    """
    from repro.models.attention import PagedKVCache

    daxes = batch_axes(mesh)
    dsize = _axis_size(mesh, daxes)
    model = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1

    def rule(leaf):
        parts: list = [None] * leaf.ndim
        is_pool = (n_pages is not None and leaf.ndim >= 3
                   and leaf.shape[1] == n_pages + 1)
        is_slot = (not is_pool and leaf.ndim >= 2
                   and leaf.shape[1] == n_slots)
        if is_slot and dsize > 1 and n_slots % dsize == 0:
            parts[1] = daxes
        if leaf.ndim >= 5 and model > 1 and leaf.shape[3] % model == 0:
            parts[3] = "model"
        return NamedSharding(mesh, P(*parts))

    def dense_pool(leaf):
        split = model > 1 and n_kv is not None and n_kv % model == 0
        return NamedSharding(mesh, P(*[None] * (leaf.ndim - 1),
                                     "model" if split else None))

    def node(x):
        if isinstance(x, PagedKVCache):
            return jax.tree_util.tree_map(dense_pool, x)
        return rule(x)

    return jax.tree_util.tree_map(
        node, caches, is_leaf=lambda x: isinstance(x, PagedKVCache))


def cache_shardings(caches: PyTree, mesh: Mesh) -> PyTree:
    """Decode/prefill cache shardings.  Stacked cache leaves are
    [G, B, ...]: batch over the data axes; for KV-style leaves
    [G, B, S, n_kv, hd] the kv-head axis goes over "model" (TP attention
    keeps each head's cache where its projection shard lives)."""
    daxes = batch_axes(mesh)
    dsize = _axis_size(mesh, daxes)
    model = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1

    def rule(leaf):
        parts: list = [None] * leaf.ndim
        if leaf.ndim >= 2 and dsize > 1 and leaf.shape[1] % dsize == 0:
            parts[1] = daxes
        if leaf.ndim >= 5 and model > 1 and leaf.shape[3] % model == 0:
            parts[3] = "model"
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map(rule, caches)
