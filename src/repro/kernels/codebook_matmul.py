"""Pallas TPU kernel: codebook-dequant matmul for quantized serving.

y[M, N] = x[M, Kd] · W  where W is stored as uint8 codebook indices
idx[Kd, N] plus a K-entry float codebook — the packed format emitted by
``repro.core.compression``.  The quantized weights are **never
materialized in HBM at float width**: each grid step dequantizes one
[bk, bn] index tile inside VMEM and feeds the MXU.

This is the memory-roofline payoff of quantization at serve time: HBM
weight traffic per step drops from 2 bytes/weight (bf16) to 1 byte
(uint8 idx; 4-bit packing halves it again — see ops.py), which directly
scales the decode-shape memory term (§Roofline).

Dequant: ``kernels.unpack.dequant_tile``, a compare+select chain over
the K codebook entries (Mosaic refuses small-table gathers).

Grid: (M/bm, N/bn, Kd/bk), k innermost; f32 accumulation directly in the
revisited output block (sequential TPU grid ⇒ safe).

For the bit-packed index operand (the end-to-end serve path — bits/8
bytes/weight instead of this kernel's 1 byte/weight uint8 indices) see
``codebook_matmul_packed.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.unpack import dequant_tile


def _kernel(x_ref, idx_ref, cb_ref, o_ref, *, bk: int, bn: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                    # [bm, bk]
    idx = idx_ref[...].astype(jnp.int32)              # [bk, bn] uint8/int32
    w = dequant_tile(idx, cb_ref[...])                # [bk, bn]
    o_ref[...] += jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                          preferred_element_type=jnp.float32)


def codebook_matmul_pallas(
    x: jax.Array,            # [M, Kd]
    idx: jax.Array,          # [Kd, N] integer codebook indices
    codebook: jax.Array,     # [K] float
    *,
    bm: int = 128, bn: int = 128, bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, kd = x.shape
    kd2, n = idx.shape
    assert kd == kd2, (kd, kd2)
    k_entries = codebook.shape[0]

    pm, pn, pk = (-m) % bm, (-n) % bn, (-kd) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    ip = jnp.pad(idx, ((0, pk), (0, pn)))
    gm, gn, gk = xp.shape[0] // bm, ip.shape[1] // bn, xp.shape[1] // bk

    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, bn=bn),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, k_entries), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], ip.shape[1]),
                                       jnp.float32),
        interpret=interpret,
    )(xp, ip, codebook.reshape(1, -1))
    return out[:m, :n].astype(x.dtype)
