"""Pallas TPU kernel: *transposed* codebook matmul over bit-packed indices
— the fused tied-embedding LM-head route (an untied head is stored
[D, V] and already serves through the forward packed kernel).

y[M, V] = x[M, D] · W.T where W [V, D] is stored bit-packed.  Before this
kernel the tied LM head was dequant-then-dot: the full bf16/f32 embedding
matrix was materialized every decode step.  Here the packed words are the
HBM-resident operand end to end — each grid step DMAs one word tile into
VMEM, unpacks it with a shift+mask (``kernels.unpack``), LUT-dequantizes,
and feeds the MXU with a transposed contraction (``dot_general`` over the
D axis) — exactly ``bits_per_index(K)/8`` bytes/weight of index traffic,
same as the forward packed kernel.

Two word layouts are accepted (``order``):

* ``"kd"``  — ``pack_indices_2d`` over the leaf's own (V, D) view:
  ``pidx[⌈V/lanes⌉, D]``; word (w, d) holds rows w·lanes+l of column d.
  V is the *output* axis here, so ``bn`` must be a multiple of ``lanes``.
* ``"row"`` — ``pack_rows``: ``pidx[V, ⌈D/lanes⌉]``; word (v, w) holds
  columns w·lanes+l of row v.  This is the serving layout for embedding
  tables (shared with the fused gather kernel), packing the *reduction*
  axis: ``bk`` must be a multiple of ``lanes``.

Grid: (M/bm, V/bn, D/bk), k innermost; f32 accumulation in the revisited
output block (sequential TPU grid ⇒ safe).  Padding is benign: padded x
columns are zero so garbage weights decoded from padded words contribute
0; padded V rows are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compression import bits_per_index
from repro.kernels.unpack import (dequant_tile, unpack_words_axis0,
                                  unpack_words_axis1)


def _kernel(x_ref, pidx_ref, cb_ref, o_ref, *, bits: int, order: str):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                    # [bm, bk]
    words = pidx_ref[...]                             # see below

    if order == "kd":
        # words [bnw, bk]: lanes expand along the V (output) axis.
        idx = unpack_words_axis0(words, bits)         # [bn, bk]
    else:
        # words [bn, bkw]: lanes expand along the D (reduction) axis.
        idx = unpack_words_axis1(words, bits)         # [bn, bk]
    w = dequant_tile(idx, cb_ref[...])                # [bn, bk]
    # y[bm, bn] += x[bm, bk] · w[bn, bk].T — contract the D axis.
    o_ref[...] += jax.lax.dot_general(
        x.astype(jnp.float32), w.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def codebook_matmul_packed_t_pallas(
    x: jax.Array,            # [M, D]
    pidx: jax.Array,         # packed indices of W [V, D]; layout per order
    codebook: jax.Array,     # [K] float
    n_out: int,              # V — the true output dim (not derivable from
                             # the padded word rows in the "kd" order)
    *,
    order: str = "kd",
    bm: int = 128, bn: int = 128, bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, d = x.shape
    k_entries = codebook.shape[0]
    bits = bits_per_index(k_entries)
    lanes = 32 // bits
    if order not in ("kd", "row"):
        raise ValueError(f"order={order!r}; choose kd|row")

    if order == "kd":
        wv, dcols = pidx.shape
        if (wv, dcols) != (-(-n_out // lanes), d):
            raise ValueError(
                f"pidx {pidx.shape} != (ceil({n_out}/{lanes}), {d}) — "
                f"operand not in pack_indices_2d layout for K={k_entries}")
        if bn % lanes:
            raise ValueError(f"bn={bn} must be a multiple of lanes={lanes} "
                             f"(bits={bits}): V is the word-packed axis")
        # Pad V up to a bn multiple (word rows to bn//lanes), D to bk.
        vp = -(-max(n_out, lanes * wv) // bn) * bn
        dp = -(-d // bk) * bk
        xp = jnp.pad(x, ((0, (-m) % bm), (0, dp - d)))
        pp = jnp.pad(pidx, ((0, vp // lanes - wv), (0, dp - d)))
        pidx_spec = pl.BlockSpec((bn // lanes, bk), lambda i, j, kk: (j, kk))
    else:
        v, wd = pidx.shape
        if (v, wd) != (n_out, -(-d // lanes)):
            raise ValueError(
                f"pidx {pidx.shape} != ({n_out}, ceil({d}/{lanes})) — "
                f"operand not in pack_rows layout for K={k_entries}")
        if bk % lanes:
            raise ValueError(f"bk={bk} must be a multiple of lanes={lanes} "
                             f"(bits={bits}): D is the word-packed axis")
        vp = -(-v // bn) * bn
        dp = -(-max(d, lanes * wd) // bk) * bk
        xp = jnp.pad(x, ((0, (-m) % bm), (0, dp - d)))
        pp = jnp.pad(pidx, ((0, vp - v), (0, dp // lanes - wd)))
        pidx_spec = pl.BlockSpec((bn, bk // lanes), lambda i, j, kk: (j, kk))

    gm, gn, gk = xp.shape[0] // bm, vp // bn, dp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, order=order),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pidx_spec,
            pl.BlockSpec((1, k_entries), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], vp), jnp.float32),
        interpret=interpret,
    )(xp, pp, codebook.reshape(1, -1))
    return out[:m, :n_out].astype(x.dtype)
