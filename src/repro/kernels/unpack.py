"""In-VMEM bit-unpack + codebook-dequant micro-library.

The three packed-serving kernels (``codebook_matmul_packed``,
``codebook_matmul_packed_t``, ``quantized_gather``) all do the same two
VPU-friendly steps on a uint32 word tile that just DMA'd into VMEM:

1. **shift+mask unpack** — each word holds ``lanes = 32 // bits``
   little-endian indices at a fixed ``bits = bits_per_index(K)`` width
   (no straddling); a broadcasted-iota shift plus an AND expands the word
   tile to an index tile;
2. **dequant** — a K-entry codebook lookup written as a compare+select
   chain over the entries (Mosaic lowers only 2-D gathers, so
   ``jnp.take(cb, idx)`` is refused on a TPU; the chain is plain VPU
   work and keeps one full tile live).

Two unpack orientations cover every packed operand layout:

* :func:`unpack_words_axis0` — words tile the *leading* axis
  (``pack_indices_2d``: word (w, n) holds rows w·lanes+l of column n) —
  the forward-matmul reduction layout;
* :func:`unpack_words_axis1` — words tile the *trailing* axis
  (``pack_rows``: word (r, w) holds columns w·lanes+l of row r) — the
  row-gather / transposed-matmul layout.

Everything here is shape-static jnp, safe both inside a Pallas kernel
body and in plain jit (the CPU reference paths reuse it).

Bit-layout contract: these unpacks must stay bit-compatible with the
host-side packers ``compression.pack_indices_2d`` / ``pack_rows`` (whose
jit-friendly inverses ``unpack_indices_2d`` / ``unpack_rows`` live in
core, deliberately not imported here to keep the kernels layer free of a
core→kernels cycle).  The pack→in-kernel-unpack roundtrips in
tests/test_packed_kernel.py and the differential matrix pin the
compatibility.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def unpack_words_axis0(words: Array, bits: int) -> Array:
    """[W, N] uint32 → [W·lanes, N] int32: lane l of word (w, n) lands at
    row w·lanes + l (the ``pack_indices_2d`` orientation)."""
    lanes = 32 // bits
    w, n = words.shape
    shifts = (jax.lax.broadcasted_iota(jnp.uint32, (w, lanes, n), 1)
              * jnp.uint32(bits))
    mask = jnp.uint32((1 << bits) - 1)
    idx = (words[:, None, :] >> shifts) & mask
    return idx.reshape(w * lanes, n).astype(jnp.int32)


def unpack_words_axis1(words: Array, bits: int) -> Array:
    """[R, W] uint32 → [R, W·lanes] int32: lane l of word (r, w) lands at
    column w·lanes + l (the ``pack_rows`` orientation)."""
    lanes = 32 // bits
    r, w = words.shape
    shifts = (jax.lax.broadcasted_iota(jnp.uint32, (r, w, lanes), 2)
              * jnp.uint32(bits))
    mask = jnp.uint32((1 << bits) - 1)
    idx = (words[:, :, None] >> shifts) & mask
    return idx.reshape(r, w * lanes).astype(jnp.int32)


def dequant_tile(idx: Array, cb: Array) -> Array:
    """[R, C] int32 indices + [1, K] codebook row → [R, C] float weights.

    ``out = cb[idx]`` as a compare+select chain over the K entries: O(K)
    VPU ops per weight (K ≤ 16 on the served routes), no gather and no
    [R, C, K] temporary, so it lowers on Mosaic at any tile shape.
    """
    out = jnp.broadcast_to(cb[:, 0:1], idx.shape)
    for k in range(1, cb.shape[-1]):
        out = jnp.where(idx == k, cb[:, k:k + 1], out)
    return out
