"""Model-wide quantized-leaf ("qleaf") abstraction.

Every multiplicative weight in the model forward — MLP leaves, attention
q/k/v/o projections, the embedding table / LM head, MoE expert tensors,
SSM and RG-LRU projections — is fetched through this module, which
understands the three storage layouts a leaf may arrive in (the
``PackedModel.serving_params`` layouts):

* dense          — ``p[name]``: the training / dense-serve layout;
* uint8 indices  — ``p[f"{name}_idx"]`` + ``p[f"{name}_cb"]``: the
  1 B/weight fallback/oracle layout (``serving_params(packed=False)``);
* bit-packed     — ``p[f"{name}_pidx"]`` uint32 words + ``p[f"{name}_cb"]``
  + the static ``p[f"{name}_layout"]`` lane metadata:
  ``bits_per_index(K)/8`` B/weight (``serving_params(packed=True)``).

Call sites pick the entry point by access pattern, and
``repro.kernels.dispatch`` picks the backend:

* :func:`qmatmul`   — ``x @ W``: the fused codebook-matmul kernels
  (Mosaic dequant-in-VMEM on TPU, jnp gather-dequant reference on CPU);
* :func:`qmatmul_t` — ``x @ W.T``: the tied-embedding LM head — the
  fused transposed packed kernel (``dispatch.packed_quantized_matmul_t``;
  the HBM operand stays packed, the [V, D] table is never inflated; an
  untied ``head_w`` is [D, V] and is already fused via :func:`qmatmul`);
* :func:`qembed`    — row gather: fused unpack + LUT dequant-on-gather
  (``dispatch.quantized_gather``; Mosaic row-gather kernel on the packed
  ``pack_rows`` layout), no dense table is materialized;
* :func:`qweight`   — the dense tensor, for einsum operands and reshaped
  factors (MoE expert stacks, MLA ``w_uk``/``w_uv``) — again an in-jit
  temporary scheduled per use.

(The pre-qleaf MLP-only aliases ``layers.mlp_matmul`` / ``mlp_weight`` /
``_has_mlp_leaf`` were removed after a deprecation PR; this module is
the only weight-fetch API.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.sharding_ctx import active_mesh

Array = jax.Array


def _split(name: str) -> str:
    """How the model axis holds weight ``name`` (``dist.sharding``'s
    Megatron placement): "row" splits its input dim, "col" its output."""
    from repro.dist.sharding import ROW_PARALLEL
    return "row" if name in ROW_PARALLEL else "col"


def has_leaf(p, name: str) -> bool:
    """True if ``name`` is present in any of the three storage layouts."""
    return name in p or f"{name}_idx" in p or f"{name}_pidx" in p


def qweight(p, name: str, dtype=None) -> Array:
    """Dense tensor fetch in the leaf's original shape (decode if
    quantized).  Under jit the decode is a temporary XLA schedules per
    use; only the packed arrays are HBM-resident inputs."""
    from repro.kernels import dispatch
    if f"{name}_pidx" in p:
        return dispatch.decode_packed_leaf(p[f"{name}_pidx"],
                                           p[f"{name}_cb"],
                                           p[f"{name}_layout"], dtype)
    if f"{name}_idx" in p:
        return dispatch.decode_leaf(p[f"{name}_idx"], p[f"{name}_cb"], dtype)
    w = p[name]
    return w.astype(dtype) if dtype is not None else w


def qmatmul(p, name: str, x: Array) -> Array:
    """``x @ <name>`` where ``<name>`` may be stored dense or quantized.

    Quantized leaves route through ``repro.kernels.dispatch`` — the packed
    uint32-word operand (or the uint8 oracle) feeds the codebook-matmul
    kernel path on TPU; the CPU reference is gather-dequant + the same
    ``x @ w`` contraction as the dense layout (bit-identical logits).
    """
    if f"{name}_pidx" in p:
        from repro.kernels import dispatch
        return dispatch.packed_quantized_matmul(
            x, p[f"{name}_pidx"], p[f"{name}_cb"],
            layout=p[f"{name}_layout"], mesh=active_mesh(), split=_split(name))
    if f"{name}_idx" in p:
        from repro.kernels import dispatch
        return dispatch.quantized_matmul(x, p[f"{name}_idx"],
                                         p[f"{name}_cb"], mesh=active_mesh(),
                                         split=_split(name))
    return x @ p[name]


def qmatmul_t(p, name: str, x: Array) -> Array:
    """``x @ <name>.T`` — the tied-embedding LM head over a [V, D] table
    (an untied head ``head_w`` is stored [D, V] and goes through
    :func:`qmatmul`, already fused).

    Packed leaves route through ``dispatch.packed_quantized_matmul_t`` —
    the fused transposed kernel on TPU reads the packed words directly
    (``bits_per_index(K)/8`` B/weight; the dense [V, D] table is never
    inflated); the CPU reference is the identical ``x @ decode.T`` graph
    as the dense layout (bit-exact logits).  uint8-oracle and dense
    leaves take the dequant-then-dot route (in-jit temporary).
    """
    if f"{name}_pidx" in p:
        from repro.kernels import dispatch
        return dispatch.packed_quantized_matmul_t(
            x, p[f"{name}_pidx"], p[f"{name}_cb"],
            layout=p[f"{name}_layout"], mesh=active_mesh())
    return x @ qweight(p, name).T


def qembed(p, name: str, tokens: Array) -> Array:
    """Row gather ``<name>[tokens]`` — embedding lookup.

    Packed layout: gather the token's uint32 word row, shift+mask the
    lane, LUT through the codebook (``dispatch.quantized_gather``) — the
    dense [V, D] table is never materialized.
    """
    if f"{name}_pidx" in p:
        from repro.kernels import dispatch
        return dispatch.quantized_gather(tokens, p[f"{name}_pidx"],
                                         p[f"{name}_cb"],
                                         layout=p[f"{name}_layout"],
                                         mesh=active_mesh())
    if f"{name}_idx" in p:
        idx = p[f"{name}_idx"][tokens].astype(jnp.int32)
        return p[f"{name}_cb"][idx]
    return p[name][tokens]
