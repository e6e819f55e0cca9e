"""Backend dispatch for quantized serving — the layer between the
PackedModel artifact and the kernels.

One call site (``models.qleaf`` — the model-wide quantized-leaf
abstraction every MLP/attention/embedding/MoE/SSM weight fetch goes
through; ``launch/serve.py --packed``) routes every codebook matmul and
embedding gather here; this module picks the implementation:

* ``pallas``            — the Mosaic kernels (dequant-in-VMEM; TPU only):
  ``codebook_matmul`` for uint8 indices, ``codebook_matmul_packed`` for
  the bit-packed uint32 word operand, ``codebook_matmul_packed_t`` for
  the fused transposed LM head, ``quantized_gather`` for the row-packed
  embedding table;
* ``pallas_interpret``  — same kernel bodies, Python interpreter (CPU
  correctness checks; slow);
* ``ref``               — pure-jnp gather-dequant + dot
  (``kernels.ref``) — the CPU serving default, and the allclose oracle.

Default: pallas on TPU, ref elsewhere; override with
``REPRO_KERNEL_BACKEND=pallas|pallas_interpret|ref`` or per call.

Block-size autotune (``packed_block_sizes``): the packed route picks
(bm, bn, bk) from a shape-keyed table — exact (M, Kd, N, bits) entries
first, then a roofline heuristic that separates decode shapes (M small:
one activation tile, stream the packed weights with wide bn·bk tiles)
from prefill shapes (M large: MXU-balanced 128×128×512).  Override with
``REPRO_PACKED_BLOCKS=bm,bn,bk``.

Meshes: a Mosaic kernel cannot be partitioned by the compiler, so on a
mesh of more than one device each Pallas route runs inside a
``shard_map`` whose specs this module picks per kernel from the
``mesh`` the model call site passes: matmuls split their weight's
output (``split="col"``) or reduction (``"row"``, psum'd) axis over
``model``, the LM head and the embedding gather split the vocabulary,
attention splits heads.  Where a dim does not divide the ``model`` axis
the operands replicate (each device runs the whole call).  Batch dims
always replicate.  The ``ref`` backend is plain jnp and is left to the
compiler.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compression import (PackedLayout, bits_per_index,
                                    unpack_indices_2d, unpack_rows)
from repro.kernels import ops, ref
from repro.kernels.quantized_gather import ROW_WORDS

Array = jax.Array

_BACKENDS = ("pallas", "pallas_interpret", "ref")

# jnp routes taken while a Pallas backend is selected, tallied when each
# call is traced: "dequant_dot" builds a dense weight before the dot,
# "row_gather" gathers packed word rows in XLA (the kernel's bytes, no
# dense table).  A chip run prints it; a dense model should show none.
FALLBACKS: collections.Counter = collections.Counter()


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in _BACKENDS:
            raise ValueError(f"REPRO_KERNEL_BACKEND={env!r}; "
                             f"choose from {_BACKENDS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _model_size(mesh: Optional[Mesh], backend: str) -> int:
    """0 when the call runs unmapped (no mesh, one device, or the ``ref``
    backend); else the size of the mesh's ``model`` axis (1 if absent:
    every operand replicates)."""
    if mesh is None or mesh.size == 1 or backend == "ref":
        return 0
    return dict(mesh.shape).get("model", 1)


def _shard_call(mesh: Mesh, body, in_specs, out_specs, *args):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _split_matmul(mesh: Mesh, m: int, split: Optional[str], rows_ok: bool,
                  fn, x: Array, w: Array, cb: Array) -> Array:
    """``fn(x, w, cb)`` = x[..., Kd] · W[Kd, N] inside a ``shard_map`` on
    ``mesh``; ``w`` is W's stored operand, reduction rows first.
    ``split`` is the W axis the ``model`` axis holds: "col" splits N (the
    product comes out split the same way), "row" splits Kd, x's last
    axis with it, and psums the partial products (when ``rows_ok``: the
    split falls on word boundaries); anything else replicates."""
    lead = (None,) * (x.ndim - 1)
    if m > 1 and split == "col" and w.shape[-1] % m == 0:
        return _shard_call(mesh, fn, (P(), P(None, "model"), P()),
                           P(*lead, "model"), x, w, cb)
    if m > 1 and split == "row" and rows_ok:
        return _shard_call(
            mesh, lambda x, w, c: jax.lax.psum(fn(x, w, c), "model"),
            (P(*lead, "model"), P("model", None), P()), P(), x, w, cb)
    return _shard_call(mesh, fn, (P(), P(), P()), P(), x, w, cb)


def _heads_spec(ndim: int, axis: int, split: bool) -> P:
    """``model`` on ``axis`` of an ``ndim`` operand (replicated if not
    ``split``)."""
    return P(*["model" if split and d == axis else None
               for d in range(ndim)])


# ---------------------------------------------------------------------------
# Packed-route block-size autotune
# ---------------------------------------------------------------------------

# Exact-shape entries (M, Kd, N, bits) → (bm, bn, bk), seeded from the
# roofline model for the bench/serve shapes; extend by measuring sweeps
# with REPRO_PACKED_BLOCKS and recording winners here.
_PACKED_BLOCK_TABLE: Dict[Tuple[int, int, int, int],
                          Tuple[int, int, int]] = {
    (256, 2048, 512, 4): (128, 128, 512),   # bench prefill shape
    (64, 1024, 256, 4): (64, 256, 512),     # bench mid shape
    (1, 2048, 512, 4): (8, 512, 1024),      # single-request decode
    # Transposed LM-head route (packed_block_sizes_t keys on (M, D, V)):
    # decode micro-batch against a row-packed 1024-vocab head (bench
    # shape codebook_matmul_packed_t_*).
    (8, 256, 1024, 4): (8, 256, 256),
}


def packed_block_table() -> Dict[Tuple[int, int, int, int],
                                 Tuple[int, int, int]]:
    """The exact-shape autotune entries (copy).  Public so the static
    auditor (``repro.analysis.vmem``) can lint every committed entry —
    a bad one otherwise only fails at Mosaic compile time on a TPU."""
    return dict(_PACKED_BLOCK_TABLE)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _fit_block(block: int, dim: int, quantum: int) -> int:
    """The largest multiple of ``quantum`` ≤ ``block`` that divides
    ``dim`` — so the kernel never pads (and so copies) the HBM word
    operand on every call.  ``block`` itself when ``dim`` is not a
    ``quantum`` multiple (padding is then unavoidable)."""
    if dim % quantum:
        return block
    for cand in range(block // quantum * quantum, 0, -quantum):
        if dim % cand == 0:
            return cand
    return block


def packed_block_sizes(m: int, kd: int, n: int, bits: int
                       ) -> Tuple[int, int, int]:
    """(bm, bn, bk) for the packed kernel at this shape.

    Priority: ``REPRO_PACKED_BLOCKS=bm,bn,bk`` env override → exact
    (M, Kd, N, bits) table hit → roofline heuristic.  The result always
    has bk a multiple of lanes (= 32 // bits) so word tiles never
    straddle a k-block boundary; heuristic picks also divide N and Kd
    where the (8, 128) tiling allows, so no call pads the word operand.
    """
    lanes = 32 // bits
    env = os.environ.get("REPRO_PACKED_BLOCKS")
    if env:
        try:
            bm, bn, bk = (int(v) for v in env.split(","))
        except ValueError as e:
            raise ValueError(
                f"REPRO_PACKED_BLOCKS={env!r}; expected 'bm,bn,bk'") from e
    else:
        hit = _PACKED_BLOCK_TABLE.get((m, kd, n, bits))
        if hit is not None:
            bm, bn, bk = hit
        else:
            if m <= 32:
                # Decode shape: activations fit one tile; widen the
                # weight tiles so the DMA stream of packed words stays
                # long.
                bm, bn, bk = _round_up(min(m, 32), 8), 256, 1024
            elif m <= 128:
                bm, bn, bk = 64, 128, 512
            else:
                # Prefill shape: MXU-balanced tiles.
                bm, bn, bk = 128, 128, 512
            # Don't over-pad small layers past one tile.
            bn = _fit_block(min(bn, _round_up(n, 128)), n, 128)
            # a k block is 128 lanes of x and 8 sublanes of words
            bk = _fit_block(min(bk, _round_up(kd, 128)), kd,
                            math.lcm(128, 8 * lanes))
    bk = max(lanes, bk // lanes * lanes)
    return bm, bn, bk


def packed_block_sizes_t(m: int, d: int, n_out: int, bits: int, order: str
                         ) -> Tuple[int, int, int]:
    """(bm, bn, bk) for the *transposed* packed kernel (LM head: y[M, V] =
    x[M, D]·W.T).  Reuses :func:`packed_block_sizes` keyed on the
    contraction shape (M, D, V), then re-aligns the lane-packed axis:
    ``order="kd"`` packs V (the output axis) → bn must be a lanes
    multiple; ``order="row"`` packs D (the reduction axis) → the
    [bn, bk/lanes] word block must be 128 words wide or the whole row
    (Mosaic's (8, 128) block rule), and bn·bk/lanes stays ≤ 128² words:
    the in-kernel unpack holds a [bn, bk/lanes, lanes] temporary whose
    minor ``lanes`` axis pads to 128 lanes in VMEM.  Same
    ``REPRO_PACKED_BLOCKS`` override."""
    bm, bn, bk = packed_block_sizes(m, d, n_out, bits)
    lanes = 32 // bits
    if order == "kd":
        bn = max(lanes, bn // lanes * lanes)
    else:
        bkw = min(-(-d // lanes), _round_up(-(-bk // lanes), 128))
        bk = lanes * bkw
        bn = min(bn, max(8, 128 * 128 // bkw // 8 * 8))
    return bm, bn, bk


# ---------------------------------------------------------------------------
# Paged-attention route block autotune
# ---------------------------------------------------------------------------

# Exact-shape entries (kind, feat, page_size, kv_bits) → token_tile, the
# number of KV tokens DMA'd per grid step (must divide page_size).
# ``feat`` is the per-token feature count of the tiled operand
# (n_kv·head_dim for gqa; kv_lora + rope_dim for mla; the flattened
# trailing dims for gather).  kv_bits=0 = dense pages.  Seeded from the
# bench/engine shapes; extend by measuring sweeps with
# ``REPRO_PAGED_BLOCK`` and recording winners here (BENCH_kernels.json
# tracks the timings).
_PAGED_BLOCK_TABLE: Dict[Tuple[str, int, int, int], int] = {
    # bench/engine config: n_kv=2 · head_dim=12, page_size=8
    ("gqa", 24, 8, 0): 8,
    ("gqa", 24, 8, 2): 8,
    ("gqa", 24, 8, 4): 8,
    ("gqa", 24, 8, 8): 8,
    ("gather", 24, 8, 0): 8,
    # kernel-bench GQA config: n_kv=2 · head_dim=32, page_size=8 (hd a
    # multiple of every lane count, so word rows pack without a ragged
    # tail and the bench's B/token invariant is exact)
    ("gqa", 64, 8, 0): 8,
    ("gqa", 64, 8, 2): 8,
    ("gqa", 64, 8, 4): 8,
    ("gqa", 64, 8, 8): 8,
    ("gather", 64, 8, 0): 8,
    # MLA bench config: kv_lora=32 + rope_dim=16, page_size=8
    ("mla", 48, 8, 0): 8,
    ("mla", 48, 8, 2): 8,
    ("mla", 48, 8, 4): 8,
    ("mla", 48, 8, 8): 8,
    # production-ish GQA shape: n_kv=8 · head_dim=128, page_size=16 —
    # half-page tiles keep the dense KV tile ≤ 32 KiB so double-buffered
    # DMA fits comfortably beside the accumulator scratch
    ("gqa", 1024, 16, 0): 8,
    ("gqa", 1024, 16, 4): 16,
}


def paged_block_table() -> Dict[Tuple[str, int, int, int], int]:
    """The exact-shape paged-attention autotune entries (copy) — public
    for the same reason as :func:`packed_block_table`: the vmem lint
    checks every committed entry at audit time."""
    return dict(_PAGED_BLOCK_TABLE)


def paged_token_tile(kind: str, feat: int, page_size: int, kv_bits: int
                     ) -> int:
    """Token tile for a paged-attention/page-gather kernel at this shape.

    Priority: ``REPRO_PAGED_BLOCK=<tile>`` env override → exact table
    hit → full page (the pools are built with small pages, so one page
    per grid step is the roofline default).  Always clamped to a
    divisor of ``page_size``.
    """
    env = os.environ.get("REPRO_PAGED_BLOCK")
    if env:
        try:
            tile = int(env)
        except ValueError as e:
            raise ValueError(f"REPRO_PAGED_BLOCK={env!r}; expected an int "
                             f"token tile") from e
    else:
        tile = _PAGED_BLOCK_TABLE.get((kind, feat, page_size, kv_bits),
                                      page_size)
    tile = min(tile, page_size)
    while page_size % tile:
        tile -= 1
    return tile


def paged_attention(q: Array, k_pool: Array, v_pool: Array, layer,
                    page_table: Array, pos: Array, alive: Array, *,
                    softcap: Optional[float] = None, scale: float,
                    backend: Optional[str] = None,
                    mesh: Optional[Mesh] = None) -> Array:
    """Paged GQA decode over dense KV pages: q [B,1,H,hd] + the stacked
    lane-dense pools [G, P+1, page, KV·hd] at ``layer`` → [B, 1, H·hd].

    ``ref`` (CPU serving default): the layer's pool as [P+1, page, KV,
    hd] through the jnp gather + masked-softmax math that used to live
    inline in ``models.attention`` — bit-identical to it.  Pallas
    backends: the fused scalar-prefetch online-softmax kernel
    (``kernels.paged_attention``), which reads the stacked pool where it
    lies, allclose vs ref.  On a mesh the heads split, the pools' lanes
    with them (whole kv heads per device)."""
    b = backend or default_backend()
    m = _model_size(mesh, b)
    hd = q.shape[3]
    kv = k_pool.shape[3] // hd
    if m:
        split = q.shape[2] % m == 0 and kv % m == 0
        body = functools.partial(paged_attention, softcap=softcap,
                                 scale=scale, backend=b)
        pools = _heads_spec(4, 3, split)
        return _shard_call(mesh, body,
                           (_heads_spec(4, 2, split), pools, pools, P(),
                            P(), P(), P()),
                           _heads_spec(3, 2, split), q, k_pool, v_pool,
                           layer, page_table, pos, alive)
    if b == "ref":
        def one(pool):
            lp = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
            return lp.reshape(lp.shape[:2] + (kv, hd))
        return ref.paged_attention_ref(q, one(k_pool), one(v_pool),
                                       page_table, pos, alive,
                                       softcap=softcap, scale=scale)
    page = k_pool.shape[2]
    tile = paged_token_tile("gqa", kv * hd, page, 0)
    out = ops.paged_attention(q, k_pool, v_pool, layer, page_table, pos,
                              alive, softcap=softcap, scale=scale,
                              token_tile=tile,
                              interpret=(b == "pallas_interpret"))
    return out.astype(k_pool.dtype)


def paged_attention_quant(q: Array, k_words: Array, v_words: Array,
                          k_cb: Array, v_cb: Array, page_table: Array,
                          pos: Array, alive: Array, *, bits: int,
                          head_dim: int, softcap: Optional[float] = None,
                          scale: float,
                          backend: Optional[str] = None,
                          mesh: Optional[Mesh] = None) -> Array:
    """Paged GQA decode over codebook-quantized KV pages (kv_bits/8 B per
    cached scalar): words [P+1, page, KV, Wd] uint32 + per-page codebooks
    [P+1, Gcb, K] → [B, 1, H·hd]."""
    b = backend or default_backend()
    m = _model_size(mesh, b)
    if m:
        kv = k_words.shape[2]
        split = q.shape[2] % m == 0 and kv % m == 0
        h4 = _heads_spec(4, 2, split)
        cb = _heads_spec(3, 1, split and k_cb.shape[1] == kv)
        body = functools.partial(paged_attention_quant, bits=bits,
                                 head_dim=head_dim, softcap=softcap,
                                 scale=scale, backend=b)
        return _shard_call(mesh, body, (h4, h4, h4, cb, cb, P(), P(), P()),
                           _heads_spec(3, 2, split), q, k_words, v_words,
                           k_cb, v_cb, page_table, pos, alive)
    if b == "ref":
        return ref.paged_attention_quant_ref(
            q, k_words, v_words, k_cb, v_cb, page_table, pos, alive,
            bits=bits, head_dim=head_dim, softcap=softcap, scale=scale)
    page, kv = k_words.shape[1], k_words.shape[2]
    tile = paged_token_tile("gqa", kv * head_dim, page, bits)
    out = ops.paged_attention_quant(
        q, k_words, v_words, k_cb, v_cb, page_table, pos, alive, bits=bits,
        head_dim=head_dim, softcap=softcap, scale=scale, token_tile=tile,
        interpret=(b == "pallas_interpret"))
    return out.astype(k_cb.dtype)


def mla_paged_attention(q_eff: Array, q_rope: Array, c_pool: Array,
                        r_pool: Array, page_table: Array, pos: Array,
                        alive: Array, *, scale: float,
                        backend: Optional[str] = None,
                        mesh: Optional[Mesh] = None) -> Array:
    """Absorbed-MLA paged decode over dense latent pages → latent context
    [B, 1, H, kv_lora].  On a mesh the heads split; the latent pages
    (shared by every head) replicate."""
    b = backend or default_backend()
    m = _model_size(mesh, b)
    if m:
        h4 = _heads_spec(4, 2, q_eff.shape[2] % m == 0)
        body = functools.partial(mla_paged_attention, scale=scale,
                                 backend=b)
        return _shard_call(mesh, body, (h4, h4) + (P(),) * 5, h4, q_eff,
                           q_rope, c_pool, r_pool, page_table, pos, alive)
    if b == "ref":
        return ref.mla_paged_attention_ref(q_eff, q_rope, c_pool, r_pool,
                                           page_table, pos, alive,
                                           scale=scale)
    page = c_pool.shape[1]
    feat = c_pool.shape[2] + r_pool.shape[2]
    tile = paged_token_tile("mla", feat, page, 0)
    out = ops.mla_paged_attention(q_eff, q_rope, c_pool, r_pool, page_table,
                                  pos, alive, scale=scale, token_tile=tile,
                                  interpret=(b == "pallas_interpret"))
    return out.astype(c_pool.dtype)


def mla_paged_attention_quant(q_eff: Array, q_rope: Array, c_words: Array,
                              r_words: Array, c_cb: Array, r_cb: Array,
                              page_table: Array, pos: Array, alive: Array,
                              *, bits: int, kv_lora: int, rope_dim: int,
                              scale: float,
                              backend: Optional[str] = None,
                              mesh: Optional[Mesh] = None) -> Array:
    """Absorbed-MLA paged decode over quantized latent pages (heads split
    on a mesh, as :func:`mla_paged_attention`)."""
    b = backend or default_backend()
    m = _model_size(mesh, b)
    if m:
        h4 = _heads_spec(4, 2, q_eff.shape[2] % m == 0)
        body = functools.partial(mla_paged_attention_quant, bits=bits,
                                 kv_lora=kv_lora, rope_dim=rope_dim,
                                 scale=scale, backend=b)
        return _shard_call(mesh, body, (h4, h4) + (P(),) * 7, h4, q_eff,
                           q_rope, c_words, r_words, c_cb, r_cb, page_table,
                           pos, alive)
    if b == "ref":
        return ref.mla_paged_attention_quant_ref(
            q_eff, q_rope, c_words, r_words, c_cb, r_cb, page_table, pos,
            alive, bits=bits, kv_lora=kv_lora, rope_dim=rope_dim,
            scale=scale)
    page = c_words.shape[1]
    tile = paged_token_tile("mla", kv_lora + rope_dim, page, bits)
    out = ops.mla_paged_attention_quant(
        q_eff, q_rope, c_words, r_words, c_cb, r_cb, page_table, pos,
        alive, bits=bits, kv_lora=kv_lora, rope_dim=rope_dim, scale=scale,
        token_tile=tile, interpret=(b == "pallas_interpret"))
    return out.astype(c_cb.dtype)


def page_gather(pool: Array, page_table: Array, alive: Array, *,
                heads: int = 0, backend: Optional[str] = None,
                mesh: Optional[Mesh] = None) -> Array:
    """Per-slot logical KV view [B, max_pages·page, ...] with dead slots
    masked to the trash page — the standalone gather (the fused decode
    kernels above subsume it on the hot path).  ``heads``: the number of
    kv heads a lane-dense pool [P+1, page, heads·hd] holds side by side
    (0: none); on a mesh such a pool splits its lanes by head, and a
    pool [P+1, page, KV, ·] its kv-head axis."""
    b = backend or default_backend()
    m = _model_size(mesh, b)
    if m:
        if heads:
            spec = _heads_spec(pool.ndim, pool.ndim - 1, heads % m == 0)
        else:
            spec = _heads_spec(pool.ndim, 2,
                               pool.ndim == 4 and pool.shape[2] % m == 0)
        body = functools.partial(page_gather, backend=b)
        return _shard_call(mesh, body, (spec, P(), P()), spec, pool,
                           page_table, alive)
    if b == "ref":
        return ref.gather_pages_ref(pool, page_table, alive)
    return ops.page_gather(pool, page_table, alive,
                           interpret=(b == "pallas_interpret"))


# ---------------------------------------------------------------------------
# Blockwise-prefill route block autotune
# ---------------------------------------------------------------------------

# Exact-shape entries (kind, feat) → token_tile, the number of stored KV
# rows DMA'd per grid step of the blockwise-prefill kernel.  ``kind`` is
# "dense" (f32/bf16 view rows) or "quant" (packed uint32 word rows);
# ``feat`` is the per-token, per-kv-head feature count of the tiled
# operand (head_dim for gqa; kv_lora + rope_dim for the expanded-MLA
# latent-derived keys).  The quant route additionally clamps the tile to
# a divisor of ``page_size`` so a tile's codebook is one page's.  Seeded
# from the bench/test shapes; extend by measuring sweeps with
# ``REPRO_PREFILL_BLOCK`` and recording winners here.
_PREFILL_BLOCK_TABLE: Dict[Tuple[str, int], int] = {
    ("dense", 12): 64,        # bench/engine mixed config head_dim
    ("dense", 8): 64,         # bf16 engine config head_dim
    ("dense", 44): 64,        # mla expanded keys: nope 32 + rope 12
    ("quant", 12): 8,         # kv_bits>0 pages, page_size=8 geometry
}

DEFAULT_PREFILL_TILE = 64


def prefill_block_table() -> Dict[Tuple[str, int], int]:
    """The exact-shape blockwise-prefill autotune entries (copy) — public
    so the static auditor's VMEM lint checks every committed entry, same
    contract as :func:`packed_block_table`/:func:`paged_block_table`."""
    return dict(_PREFILL_BLOCK_TABLE)


def prefill_token_tile(kind: str, feat: int,
                       page_size: Optional[int] = None) -> int:
    """KV-row tile for a blockwise-prefill kernel at this shape.

    Priority: ``REPRO_PREFILL_BLOCK=<tile>`` env override → exact
    (kind, feat) table hit → :data:`DEFAULT_PREFILL_TILE`.  When
    ``page_size`` is given (the quantized-page route) the tile is
    clamped to a divisor of it so no tile straddles a codebook
    boundary.
    """
    env = os.environ.get("REPRO_PREFILL_BLOCK")
    if env:
        try:
            tile = int(env)
        except ValueError as e:
            raise ValueError(f"REPRO_PREFILL_BLOCK={env!r}; expected an "
                             f"int token tile") from e
    else:
        tile = _PREFILL_BLOCK_TABLE.get((kind, feat), DEFAULT_PREFILL_TILE)
    tile = max(1, tile)
    if page_size is not None:
        tile = min(tile, page_size)
        while page_size % tile:
            tile -= 1
    return tile


def blockwise_prefill_attention(q: Array, k: Array, v: Array, q_pos: Array,
                                k_pos: Array, *,
                                window: Optional[int] = None,
                                softcap: Optional[float] = None,
                                scale: float,
                                backend: Optional[str] = None,
                                mesh: Optional[Mesh] = None) -> Array:
    """Chunked-prompt prefill attention: q [B,C,H,hd] (the C new tokens)
    vs. a stored K/V view k [B,S,KV,hd] / v [B,S,KV,vd] with 1-D int32
    positions q_pos [C] / k_pos [S] → [B,C,H,vd] in the view dtype.

    Visibility is purely position-derived (``k_pos <= q_pos`` and the
    optional sliding ``window``); rows past the valid prefix carry
    ``ref.POS_SENTINEL`` and mask to exact zero probability, so the
    engine's fixed-capacity page view and the oracle's growing buffer
    produce bit-identical chunks.  The view is padded to a tile multiple
    here — identically on every backend — so ref and Pallas reduce over
    the same tile partition.  On a mesh the heads split."""
    b = backend or default_backend()
    m = _model_size(mesh, b)
    if m:
        h4 = _heads_spec(4, 2, q.shape[2] % m == 0 and k.shape[2] % m == 0)
        body = functools.partial(blockwise_prefill_attention, window=window,
                                 softcap=softcap, scale=scale, backend=b)
        return _shard_call(mesh, body, (h4, h4, h4, P(), P()), h4, q, k, v,
                           q_pos, k_pos)
    tile = prefill_token_tile("dense", k.shape[-1])
    s = k.shape[1]
    k_pos = k_pos.astype(jnp.int32)
    pad = (-s) % tile
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.concatenate(
            [k_pos, jnp.full((pad,), ref.POS_SENTINEL, jnp.int32)])
    if b == "ref":
        out = ref.blockwise_prefill_ref(q, k, v, q_pos, k_pos,
                                        window=window, softcap=softcap,
                                        scale=scale, token_tile=tile)
    else:
        out = ops.blockwise_prefill(q, k, v, q_pos, k_pos, window=window,
                                    softcap=softcap, scale=scale,
                                    token_tile=tile,
                                    interpret=(b == "pallas_interpret"))
    return out.astype(v.dtype)


def blockwise_prefill_attention_quant(q: Array, k_words: Array,
                                      v_words: Array, k_cb: Array,
                                      v_cb: Array, q_pos: Array,
                                      k_pos: Array, *, page_size: int,
                                      bits: int, head_dim: int,
                                      window: Optional[int] = None,
                                      softcap: Optional[float] = None,
                                      scale: float,
                                      backend: Optional[str] = None,
                                      mesh: Optional[Mesh] = None
                                      ) -> Array:
    """Chunked-prompt prefill over the slot's codebook-quantized pages:
    word view [B, S, KV, Wd] uint32 (S = n_pages·page_size, logical row
    order) + per-page codebooks [B, n_pages, Gcb, K] → [B, C, H,
    head_dim] in the codebook dtype.  kv_bits/8 B per cached scalar of
    KV traffic on the Pallas backends; same position-derived masking as
    the dense route (stale rows of reused pages carry sentinel
    positions).  On a mesh the heads split."""
    b = backend or default_backend()
    m = _model_size(mesh, b)
    if m:
        kv = k_words.shape[2]
        split = q.shape[2] % m == 0 and kv % m == 0
        h4 = _heads_spec(4, 2, split)
        cb = _heads_spec(4, 2, split and k_cb.shape[2] == kv)
        body = functools.partial(
            blockwise_prefill_attention_quant, page_size=page_size,
            bits=bits, head_dim=head_dim, window=window, softcap=softcap,
            scale=scale, backend=b)
        return _shard_call(mesh, body, (h4, h4, h4, cb, cb, P(), P()), h4,
                           q, k_words, v_words, k_cb, v_cb, q_pos, k_pos)
    tile = prefill_token_tile("quant", head_dim, page_size=page_size)
    s = k_words.shape[1]
    if s % page_size:
        raise ValueError(f"quantized view rows {s} not a multiple of "
                         f"page_size={page_size}")
    k_pos = k_pos.astype(jnp.int32)
    if b == "ref":
        out = ref.blockwise_prefill_quant_ref(
            q, k_words, v_words, k_cb, v_cb, q_pos, k_pos,
            page_size=page_size, bits=bits, head_dim=head_dim,
            window=window, softcap=softcap, scale=scale, token_tile=tile)
    else:
        out = ops.blockwise_prefill_quant(
            q, k_words, v_words, k_cb, v_cb, q_pos, k_pos,
            page_size=page_size, bits=bits, head_dim=head_dim,
            window=window, softcap=softcap, scale=scale, token_tile=tile,
            interpret=(b == "pallas_interpret"))
    return out.astype(k_cb.dtype)


def codebook_matmul(x: Array, idx: Array, codebook: Array, *,
                    backend: Optional[str] = None,
                    bm: int = 128, bn: int = 128, bk: int = 512) -> Array:
    """y[M, N] = x[M, Kd] · codebook[idx[Kd, N]] on the chosen backend."""
    b = backend or default_backend()
    if b == "ref":
        return ref.codebook_matmul_ref(x, idx, codebook)
    return ops.codebook_matmul(x, idx, codebook, bm=bm, bn=bn, bk=bk,
                               interpret=(b == "pallas_interpret"))


def packed_codebook_matmul(x: Array, pidx: Array, codebook: Array, *,
                           layout: Optional[PackedLayout] = None,
                           backend: Optional[str] = None,
                           blocks: Optional[Tuple[int, int, int]] = None,
                           ) -> Array:
    """y[M, N] = x[M, Kd] · codebook[unpack(pidx)] with the bit-packed
    uint32 word operand (``pack_indices_2d`` layout) HBM-resident end to
    end — bits_per_index(K)/8 bytes/weight of index traffic.

    ``layout`` (the static lane metadata ``serving_params(packed=True)``
    emits) is validated against the operands when given; block sizes come
    from :func:`packed_block_sizes` unless ``blocks`` overrides.
    """
    k = int(codebook.shape[-1])
    bits = bits_per_index(k)
    m, kd = x.shape
    wk, n = pidx.shape
    if layout is not None:
        if (layout.kd, layout.n, layout.k) != (kd, n, k):
            raise ValueError(f"packed layout {layout} does not match "
                             f"operands x[{m},{kd}] pidx[...,{n}] cb[{k}]")
        bits = layout.bits
    # Validate the word count on every backend — the ref route would
    # otherwise silently truncate a mismatched (stale/wrong-leaf) operand.
    lanes = 32 // bits
    if wk != -(-kd // lanes):
        raise ValueError(f"pidx rows {wk} != ceil({kd}/{lanes}) — operand "
                         f"not in pack_indices_2d layout for K={k}")
    b = backend or default_backend()
    if b == "ref":
        return ref.packed_codebook_matmul_ref(x, pidx, codebook)
    bm, bn, bk = blocks or packed_block_sizes(m, kd, n, bits)
    return ops.packed_codebook_matmul(
        x, pidx, codebook, bm=bm, bn=bn, bk=bk,
        interpret=(b == "pallas_interpret"))


def quantized_matmul(x: Array, idx: Array, codebook: Array, *,
                     backend: Optional[str] = None,
                     mesh: Optional[Mesh] = None,
                     split: Optional[str] = None) -> Array:
    """Batched-x wrapper: x[..., Kd] · codebook[idx[Kd, N]] → [..., N].

    This is the serve-path entry ``models.qleaf.qmatmul`` uses when a
    param leaf is stored quantized (``<name>_idx`` + ``<name>_cb``).

    On the ``ref`` backend (the CPU serving default) the contraction is
    literally ``x @ codebook[idx]`` — the identical graph as the dense
    layout, so packed-vs-dense serving is bit-exact there.  ``mesh`` and
    ``split`` map the kernel route over devices (see
    :func:`_split_matmul`).
    """
    b = backend or default_backend()
    if b == "ref" or idx.ndim != 2:
        if b != "ref":
            FALLBACKS["dequant_dot"] += 1
        y = x @ decode_leaf(idx, codebook)
        return y.astype(x.dtype)
    m = _model_size(mesh, b)
    if m:
        fn = functools.partial(quantized_matmul, backend=b)
        return _split_matmul(mesh, m, split, x.shape[-1] % m == 0, fn, x,
                             idx, codebook)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = codebook_matmul(x2, idx, codebook, backend=b)
    return y.reshape(lead + (idx.shape[-1],)).astype(x.dtype)


def packed_quantized_matmul(x: Array, pidx: Array, codebook: Array, *,
                            layout: Optional[PackedLayout] = None,
                            backend: Optional[str] = None,
                            mesh: Optional[Mesh] = None,
                            split: Optional[str] = None) -> Array:
    """Batched-x wrapper over :func:`packed_codebook_matmul` — the serve-
    path entry ``models.qleaf.qmatmul`` uses for the ``<name>_pidx``
    layout.  Same bit-exact dense-graph property on ``ref`` as
    :func:`quantized_matmul`; non-matrix layouts (``layout.shape`` set)
    always take the dequant-then-dot route.  ``mesh`` and ``split`` map
    the kernel route over devices (see :func:`_split_matmul`)."""
    b = backend or default_backend()
    nd = layout is not None and (layout.shape is not None
                                 or layout.order != "kd")
    if b == "ref" or pidx.ndim != 2 or nd:
        if b != "ref":
            FALLBACKS["dequant_dot"] += 1
        if layout is None:
            raise ValueError("packed_quantized_matmul needs the static "
                             "PackedLayout on the dequant route")
        y = x @ decode_packed_leaf(pidx, codebook, layout)
        return y.astype(x.dtype)
    m = _model_size(mesh, b)
    if m:
        lanes = 32 // bits_per_index(int(codebook.shape[-1]))

        def fn(x, w, c):
            loc = (None if layout is None else dataclasses.replace(
                layout, kd=x.shape[-1], n=w.shape[-1]))
            return packed_quantized_matmul(x, w, c, layout=loc, backend=b)
        return _split_matmul(mesh, m, split, x.shape[-1] % (m * lanes) == 0,
                             fn, x, pidx, codebook)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = packed_codebook_matmul(x2, pidx, codebook, layout=layout,
                               backend=b)
    return y.reshape(lead + (y.shape[-1],)).astype(x.dtype)


def packed_quantized_matmul_t(x: Array, pidx: Array, codebook: Array, *,
                              layout: PackedLayout,
                              backend: Optional[str] = None,
                              blocks: Optional[Tuple[int, int, int]] = None,
                              mesh: Optional[Mesh] = None) -> Array:
    """y[..., V] = x[..., D] · codebook[unpack(pidx)].T — the fused
    transposed (tied/untied LM-head) route over a packed [V, D] leaf.

    The packed word operand — ``pack_indices_2d`` (``layout.order="kd"``)
    or ``pack_rows`` (``"row"``, the embedding serving layout shared with
    the fused gather) — stays HBM-resident on the Pallas backends:
    ``bits_per_index(K)/8`` bytes/weight, no dense [V, D] temporary.  On
    the ``ref`` backend (CPU serving default) the contraction is literally
    ``x @ decode.T`` — the identical graph as the dense layout, so
    packed-vs-dense logits are bit-exact there.  On a mesh the vocabulary
    splits over ``model`` (the embedding table's placement), and so do
    the logits.
    """
    b = backend or default_backend()
    if b == "ref" or pidx.ndim != 2 or layout.shape is not None \
            or codebook.ndim != 1:
        if b != "ref":
            FALLBACKS["dequant_dot"] += 1
        w = decode_packed_leaf(pidx, codebook, layout)
        y = x @ jnp.matrix_transpose(w)
        return y.astype(x.dtype)
    m = _model_size(mesh, b)
    if m:
        rows = pidx.shape[0]        # V ("row") or ⌈V/lanes⌉ ("kd") words
        split = m > 1 and rows % m == 0 and (
            layout.order == "row" or layout.kd == rows * layout.lanes)
        loc = (dataclasses.replace(layout, kd=layout.kd // m) if split
               else layout)
        fn = functools.partial(packed_quantized_matmul_t, layout=loc,
                               backend=b, blocks=blocks)
        vocab = P(*(None,) * (x.ndim - 1), "model") if split else P()
        return _shard_call(mesh, fn,
                           (P(), P("model", None) if split else P(), P()),
                           vocab, x, pidx, codebook)
    if pidx.shape != layout.word_shape:
        raise ValueError(f"pidx {pidx.shape} != layout word shape "
                         f"{layout.word_shape} ({layout})")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    bm, bn, bk = blocks or packed_block_sizes_t(
        x2.shape[0], layout.n, layout.kd, layout.bits, layout.order)
    y = ops.packed_codebook_matmul_t(
        x2, pidx, codebook, layout.kd, order=layout.order, bm=bm, bn=bn,
        bk=bk,
        interpret=(b == "pallas_interpret"))
    return y.reshape(lead + (layout.kd,)).astype(x.dtype)


def quantized_gather(tokens: Array, pidx: Array, codebook: Array, *,
                     layout: PackedLayout,
                     backend: Optional[str] = None,
                     mesh: Optional[Mesh] = None) -> Array:
    """Embedding dequant-on-gather: rows ``codebook[unpack(pidx)[tokens]]``
    without ever materializing the dense [V, D] table.

    ``layout.order == "row"`` (the serving layout —
    :func:`~repro.core.compression.pack_rows`, [V, ⌈D/lanes⌉] uint32): a
    token's lookup reads its contiguous packed word row — exactly
    ``bits_per_index(K)/8`` bytes per gathered weight.  On TPU this is the
    Mosaic kernel (``kernels.quantized_gather``: one row DMA per token →
    shift+mask → codebook select) when a word row is ``ROW_WORDS`` wide,
    the only width Mosaic DMAs row by row (the refusals at the other
    widths are listed beside ``ROW_WORDS``); the jnp route (XLA word-row
    gather + unpack, the same bytes) serves other widths and is the CPU
    reference — a pure gather, so it is bit-exact vs the dense table on
    every backend.

    ``layout.order == "kd"`` (the pre-row-pack column layout,
    :func:`~repro.core.compression.pack_indices_2d` over the vocab axis):
    retained jnp fallback — gathers one full uint32 word per embedding
    *column* (4 B/weight), shift+masks the token's lane.  A 2-D codebook
    is per-group ([G, K]) — not needed for the root embedding table.

    On a mesh the kernel route splits the vocabulary over ``model`` (the
    table's placement): each device looks up the tokens in its own rows,
    zeroes the rest, and a psum assembles the rows exactly.
    """
    tokens = tokens.astype(jnp.int32)
    b = backend or default_backend()
    m = _model_size(mesh, b)
    if m and layout.order == "row" and pidx.ndim == 2 \
            and codebook.ndim == 1:
        if m > 1 and layout.kd % m == 0:
            loc = dataclasses.replace(layout, kd=layout.kd // m)

            def fn(t, w, c):
                t = t - jax.lax.axis_index("model") * loc.kd
                hit = (t >= 0) & (t < loc.kd)
                rows = quantized_gather(jnp.where(hit, t, 0), w, c,
                                        layout=loc, backend=b)
                return jax.lax.psum(
                    jnp.where(hit[..., None], rows, 0), "model")
            return _shard_call(mesh, fn, (P(), P("model", None), P()), P(),
                               tokens, pidx, codebook)
        fn = functools.partial(quantized_gather, layout=layout, backend=b)
        return _shard_call(mesh, fn, (P(), P(), P()), P(), tokens, pidx,
                           codebook)
    kernel = b == "pallas_interpret" or (b == "pallas"
                                         and pidx.shape[-1] == ROW_WORDS)
    if layout.order == "row" and kernel and pidx.ndim == 2 \
            and codebook.ndim == 1:
        lead = tokens.shape
        out = ops.quantized_gather(tokens.reshape(-1), pidx, codebook,
                                   layout.n, interpret=(b != "pallas"))
        rows = out.reshape(lead + (layout.n,))
    elif layout.order == "row":
        if b != "ref":
            FALLBACKS["row_gather"] += 1
        words = pidx[tokens]                         # [..., ⌈D/lanes⌉]
        idx = unpack_rows(words, layout.n, layout.k)
        rows = codebook[idx]
    else:
        if b != "ref":
            FALLBACKS["row_gather"] += 1
        mask = jnp.uint32((1 << layout.bits) - 1)
        words = pidx[tokens // layout.lanes]         # [..., D] uint32
        lane = (tokens % layout.lanes).astype(jnp.uint32)
        idx = (words >> (lane[..., None] * jnp.uint32(layout.bits))) & mask
        rows = codebook[idx.astype(jnp.int32)]
    # Cast f32 codebook values back to the table's original dtype so the
    # embedding keeps anchoring the residual-stream dtype (bf16 models).
    return rows if layout.dtype is None else rows.astype(layout.dtype)


def decode_leaf(idx: Array, codebook: Array, dtype=None) -> Array:
    """Materialize a dense weight from (indices, codebook) — the fallback
    for call sites without a fused kernel.  A 2-D codebook is per-group
    ([G, K] against idx [G, ...]): gathered group-wise."""
    idx = idx.astype(jnp.int32)
    if codebook.ndim == 2:
        w = jax.vmap(lambda i, c: c[i])(idx, codebook)
    else:
        w = codebook[idx]
    return w.astype(dtype) if dtype is not None else w


def decode_packed_leaf(pidx: Array, codebook: Array, layout: PackedLayout,
                       dtype=None) -> Array:
    """Materialize a dense weight from the bit-packed word operand
    (``pack_indices_2d`` layout, or ``pack_rows`` when
    ``layout.order == "row"``; grouped leaves carry a leading G axis).
    Non-matrix leaves (``layout.shape`` set — e.g. MoE expert stacks
    [E, D, F] packed as (E·D, F)) are reshaped back to the dense shape."""
    if layout.order == "row":
        idx = unpack_rows(pidx, layout.n, layout.k)
    elif pidx.ndim == 3:
        idx = jax.vmap(lambda w: unpack_indices_2d(w, layout.kd,
                                                   layout.k))(pidx)
    else:
        idx = unpack_indices_2d(pidx, layout.kd, layout.k)
    if dtype is None:
        dtype = layout.dtype      # original leaf dtype (None on old layouts)
    w = decode_leaf(idx, codebook, dtype)
    if layout.shape is not None:
        w = w.reshape(w.shape[:-2] + tuple(layout.shape))
    return w


def decode_params(tree: Any) -> Any:
    """In-jit dense reconstruction of a ``serving_params``-layout tree:
    every ``<name>_idx``/``<name>_cb`` (or ``<name>_pidx``/``<name>_cb``/
    ``<name>_layout``) group collapses to a dense ``<name>`` leaf.  Under
    jit only the packed arrays are HBM-resident inputs; the dense weights
    are temporaries XLA schedules per use."""
    if isinstance(tree, dict):
        out = {}
        for key, val in tree.items():
            if key.endswith("_idx") and not key.endswith("_pidx"):
                name = key[:-4]
                out[name] = decode_leaf(val, tree[f"{name}_cb"])
            elif key.endswith("_pidx"):
                name = key[:-5]
                out[name] = decode_packed_leaf(val, tree[f"{name}_cb"],
                                               tree[f"{name}_layout"])
            elif key.endswith("_cb") and (f"{key[:-3]}_idx" in tree
                                          or f"{key[:-3]}_pidx" in tree):
                continue
            elif key.endswith("_layout") and f"{key[:-7]}_pidx" in tree:
                continue
            else:
                out[key] = decode_params(val)
        return out
    if isinstance(tree, (tuple, list)):
        return type(tree)(decode_params(v) for v in tree)
    return tree
