"""Pallas TPU kernel: embedding dequant-on-gather over bit-packed rows.

out[T, D] = codebook[unpack(pidx[tokens])] where the embedding table
[V, D] is stored in the ``pack_rows`` layout — uint32 ``pidx[V, ⌈D/lanes⌉]``,
each word holding ``lanes = 32 // bits`` consecutive *feature-axis*
indices of one vocab row.  The token ids are a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``); each grid step serves 8 tokens with
one packed word row DMA each — ``⌈D/lanes⌉ · 4`` bytes, i.e.
``bits_per_index(K)/8`` bytes per gathered weight — then shift+mask-
unpacks the [8, ⌈D/lanes⌉] tile and dequantizes it against the K-entry
codebook in VMEM (``kernels.unpack``).

This replaces the jnp fallback over the PR-3 column-packed layout, which
gathered one full uint32 word per embedding *column* (4 B/weight): the
packed-row layout + fused kernel close the last dense-inflation gap of
the eq.-14 serving story.  The jnp route (``dispatch.quantized_gather``)
is retained as the CPU reference.

The dense [V, D] table is never materialized; the only f32 HBM write is
the [T, D] result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compression import bits_per_index
from repro.kernels.unpack import dequant_tile, unpack_words_axis1

_ROWS = 8      # tokens per grid step: one f32 sublane tile of output rows

# Mosaic DMAs one table row only when that row is exactly one 128-lane
# tile row.  Compiled for a v5e: Dw=32 and Dw=64 (K=2, K=4 at D=1024)
# rows lie in lane-padded HBM tiles and their DMA is refused ("Slice
# shape along dimension 1 must be aligned to tiling (128)"); a Dw=256
# table (K=16 at D=2048) is (8, 128)-tiled, so a one-row slice is
# refused ("... dimension 0 must be aligned to tiling (8)"); a [1, Dw] block
# breaks the (8, 128) block rule, and a [V, 1, Dw] view compiles only
# behind a relayout copy of the whole table per call.
# ``dispatch.quantized_gather`` sends the other widths to the jnp
# word-row gather, which reads the same bytes per token, and counts it.
ROW_WORDS = 128


def _kernel(tokens_ref, pidx_hbm, cb_ref, o_ref, buf, sem, *, bits: int,
            rows: int):
    base = pl.program_id(0) * rows
    # One packed word row DMA per token, straight from HBM into row r of
    # the [rows, Dw] VMEM tile (a [1, Dw] BlockSpec breaks the (8, 128)
    # block rule, so the row gather is issued by hand).
    copies = [pltpu.make_async_copy(
        pidx_hbm.at[pl.ds(tokens_ref[base + r], 1)], buf.at[pl.ds(r, 1)],
        sem.at[r]) for r in range(rows)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()
    idx = unpack_words_axis1(buf[...], bits)          # [rows, Dw·lanes]
    o_ref[...] = dequant_tile(idx, cb_ref[...])


def quantized_gather_pallas(
    tokens: jax.Array,       # [T] int32 row ids
    pidx: jax.Array,         # [V, ⌈D/lanes⌉] uint32 pack_rows words
    codebook: jax.Array,     # [K] float
    d: int,                  # true feature dim D (≤ ⌈D/lanes⌉·lanes)
    *,
    interpret: bool = False,
) -> jax.Array:
    if tokens.ndim != 1:
        raise ValueError(f"tokens must be flat [T], got {tokens.shape}")
    k_entries = codebook.shape[0]
    bits = bits_per_index(k_entries)
    lanes = 32 // bits
    v, wd = pidx.shape
    if wd != -(-d // lanes):
        raise ValueError(f"pidx cols {wd} != ceil({d}/{lanes}) — operand "
                         f"not in pack_rows layout for K={k_entries}")
    dp = wd * lanes
    t = tokens.shape[0]
    tp = -(-t // _ROWS) * _ROWS            # pad rows gather token 0
    toks = jnp.pad(tokens.astype(jnp.int32), (0, tp - t))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tp // _ROWS,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec((1, k_entries), lambda i, toks: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, dp), lambda i, toks: (i, 0)),
        scratch_shapes=[pltpu.VMEM((_ROWS, wd), jnp.uint32),
                        pltpu.SemaphoreType.DMA((_ROWS,))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, rows=_ROWS),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, dp), jnp.float32),
        interpret=interpret,
    )(toks, pidx, codebook.reshape(1, -1))
    return out[:t, :d]
