"""Pallas TPU kernel: codebook matmul over *bit-packed* indices.

y[M, N] = x[M, Kd] · W where W is stored as the ``pack_indices_2d`` word
layout — uint32 ``pidx[⌈Kd/lanes⌉, N]``, each word holding ``lanes =
32//bits`` reduction-axis indices at a fixed ``bits = bits_per_index(K)``
width (little-endian, no straddling).  The packed words are the HBM-
resident operand: each grid step DMAs one [bkw, bn] word tile into VMEM,
unpacks it to a [bkw·lanes, bn] index tile with a shift+mask (pure VPU),
dequantizes, and feeds the MXU.

This closes the serve-path gap of the eq.-14 story: HBM weight traffic per
step is exactly ``bits/8`` bytes/weight — 4 bits at K=16 (8× less than
bf16, 2× less than the uint8-index layout), 2 bits at ternary, 1 bit at
binary — plus one K-entry codebook reread per (i, j) tile.

Dequant: ``kernels.unpack.dequant_tile``, a compare+select chain over
the K codebook entries (Mosaic refuses small-table gathers).

Grid: (M/bm, N/bn, Kd/bk), k innermost; f32 accumulation in the revisited
output block (sequential TPU grid ⇒ safe).  ``bk`` must be a multiple of
``lanes`` so word tiles never straddle a k-block boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compression import bits_per_index
from repro.kernels.unpack import dequant_tile, unpack_words_axis0


def _kernel(x_ref, pidx_ref, cb_ref, o_ref, *, bits: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                    # [bm, bk]
    words = pidx_ref[...]                             # [bkw, bn] uint32

    # In-VMEM unpack: word (w, n) → lanes indices at rows w·lanes+l.
    idx = unpack_words_axis0(words, bits)             # [bk, bn]
    w = dequant_tile(idx, cb_ref[...])
    o_ref[...] += jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                          preferred_element_type=jnp.float32)


def codebook_matmul_packed_pallas(
    x: jax.Array,            # [M, Kd]
    pidx: jax.Array,         # [⌈Kd/lanes⌉, N] uint32 packed indices
    codebook: jax.Array,     # [K] float
    *,
    bm: int = 128, bn: int = 128, bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, kd = x.shape
    k_entries = codebook.shape[0]
    bits = bits_per_index(k_entries)
    lanes = 32 // bits
    wk, n = pidx.shape
    if wk != -(-kd // lanes):
        raise ValueError(f"pidx rows {wk} != ceil({kd}/{lanes}) — operand "
                         f"not in pack_indices_2d layout for K={k_entries}")
    if bk % lanes:
        raise ValueError(f"bk={bk} must be a multiple of lanes={lanes} "
                         f"(bits={bits}) so word tiles don't straddle")
    bkw = bk // lanes

    # Pad M/N with zeros and Kd up to a bk multiple.  Padded x rows are
    # zero, so whatever the zero-padded words decode to contributes 0.
    kdp = -(-max(kd, lanes * wk) // bk) * bk
    xp = jnp.pad(x, ((0, (-m) % bm), (0, kdp - kd)))
    pp = jnp.pad(pidx, ((0, kdp // lanes - wk), (0, (-n) % bn)))
    gm, gn, gk = xp.shape[0] // bm, pp.shape[1] // bn, kdp // bk

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkw, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, k_entries), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], pp.shape[1]),
                                       jnp.float32),
        interpret=interpret,
    )(xp, pp, codebook.reshape(1, -1))
    return out[:m, :n].astype(x.dtype)
