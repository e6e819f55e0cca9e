"""Jit'd public wrappers around the Pallas kernels.

On CPU (this container) kernels run in ``interpret=True`` mode — the
kernel body executes in Python for correctness validation; on TPU the same
call sites compile to Mosaic.  ``interpret=None`` → auto-detect backend.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.blockwise_prefill import (
    blockwise_prefill_pallas, blockwise_prefill_quant_pallas)
from repro.kernels.codebook_matmul import codebook_matmul_pallas
from repro.kernels.codebook_matmul_packed import codebook_matmul_packed_pallas
from repro.kernels.codebook_matmul_packed_t import (
    codebook_matmul_packed_t_pallas)
from repro.kernels.fixed_quant import fixed_quant_pallas
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.paged_attention import (
    mla_paged_attention_pallas, mla_paged_attention_quant_pallas,
    page_gather_pallas, paged_attention_pallas,
    paged_attention_quant_pallas)
from repro.kernels.quantized_gather import quantized_gather_pallas


# Kernel calls by mode, tallied when each call is traced: a chip run
# reads it to prove that no kernel ran in the Pallas interpreter.
CALLS: collections.Counter = collections.Counter()


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    CALLS["interpret" if interpret else "mosaic"] += 1
    return interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kmeans_assign_jit(w, codebook, interpret):
    return kmeans_assign_pallas(w, codebook, interpret=interpret)


def kmeans_assign(w: jax.Array, codebook: jax.Array,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused assignment + per-centroid (Σw, count). See kmeans_assign.py."""
    return _kmeans_assign_jit(w.reshape(-1), codebook,
                              _auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _codebook_matmul_jit(x, idx, codebook, bm, bn, bk, interpret):
    return codebook_matmul_pallas(x, idx, codebook, bm=bm, bn=bn, bk=bk,
                                  interpret=interpret)


def codebook_matmul(x: jax.Array, idx: jax.Array, codebook: jax.Array,
                    *, bm: int = 128, bn: int = 128, bk: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """y = x · codebook[idx] without materializing float weights in HBM."""
    return _codebook_matmul_jit(x, idx, codebook, bm, bn, bk,
                                _auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _packed_codebook_matmul_jit(x, pidx, codebook, bm, bn, bk,
                                interpret):
    return codebook_matmul_packed_pallas(x, pidx, codebook, bm=bm, bn=bn,
                                         bk=bk, interpret=interpret)


def packed_codebook_matmul(x: jax.Array, pidx: jax.Array,
                           codebook: jax.Array, *, bm: int = 128,
                           bn: int = 128, bk: int = 512,
                           interpret: Optional[bool] = None) -> jax.Array:
    """y = x · codebook[unpack(pidx)] with the ``pack_indices_2d`` uint32
    word operand HBM-resident: bits_per_index(K)/8 bytes/weight of index
    traffic (see codebook_matmul_packed.py)."""
    return _packed_codebook_matmul_jit(x, pidx, codebook, bm, bn, bk,
                                       _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("n_out", "order", "bm", "bn", "bk",
                                    "interpret"))
def _packed_codebook_matmul_t_jit(x, pidx, codebook, n_out, order, bm, bn,
                                  bk, interpret):
    return codebook_matmul_packed_t_pallas(x, pidx, codebook, n_out,
                                           order=order, bm=bm, bn=bn, bk=bk,
                                           interpret=interpret)


def packed_codebook_matmul_t(x: jax.Array, pidx: jax.Array,
                             codebook: jax.Array, n_out: int, *,
                             order: str = "kd", bm: int = 128,
                             bn: int = 128, bk: int = 512,
                             interpret: Optional[bool] = None) -> jax.Array:
    """y = x · codebook[unpack(pidx)].T — the fused transposed (LM-head)
    route; the packed word operand stays HBM-resident (see
    codebook_matmul_packed_t.py)."""
    return _packed_codebook_matmul_t_jit(x, pidx, codebook, n_out, order,
                                         bm, bn, bk,
                                         _auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("d", "interpret"))
def _quantized_gather_jit(tokens, pidx, codebook, d, interpret):
    return quantized_gather_pallas(tokens, pidx, codebook, d,
                                   interpret=interpret)


def quantized_gather(tokens: jax.Array, pidx: jax.Array,
                     codebook: jax.Array, d: int, *,
                     interpret: Optional[bool] = None) -> jax.Array:
    """rows = codebook[unpack(pidx[tokens])] — Mosaic dequant-on-gather
    over the pack_rows embedding layout: ``bits_per_index(K)/8`` HBM bytes
    per gathered weight (see quantized_gather.py)."""
    return _quantized_gather_jit(tokens, pidx, codebook, d,
                                 _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("softcap", "scale", "token_tile",
                                    "interpret"))
def _paged_attention_jit(q, k_pool, v_pool, page_table, pos, alive, softcap,
                         scale, token_tile, interpret):
    return paged_attention_pallas(q, k_pool, v_pool, page_table, pos, alive,
                                  softcap=softcap, scale=scale,
                                  token_tile=token_tile, interpret=interpret)


def paged_attention(q, k_pool, v_pool, page_table, pos, alive, *,
                    softcap=None, scale, token_tile=None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused page-gather + online-softmax GQA decode (paged_attention.py)."""
    return _paged_attention_jit(q, k_pool, v_pool, page_table, pos, alive,
                                softcap, scale, token_tile,
                                _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("bits", "head_dim", "softcap", "scale",
                                    "token_tile", "interpret"))
def _paged_attention_quant_jit(q, k_words, v_words, k_cb, v_cb, page_table,
                               pos, alive, bits, head_dim, softcap, scale,
                               token_tile, interpret):
    return paged_attention_quant_pallas(
        q, k_words, v_words, k_cb, v_cb, page_table, pos, alive, bits=bits,
        head_dim=head_dim, softcap=softcap, scale=scale,
        token_tile=token_tile, interpret=interpret)


def paged_attention_quant(q, k_words, v_words, k_cb, v_cb, page_table, pos,
                          alive, *, bits, head_dim, softcap=None, scale,
                          token_tile=None,
                          interpret: Optional[bool] = None) -> jax.Array:
    """GQA decode over codebook-quantized KV pages: kv_bits/8 B per cached
    scalar of HBM traffic, dequant in VMEM (paged_attention.py)."""
    return _paged_attention_quant_jit(q, k_words, v_words, k_cb, v_cb,
                                      page_table, pos, alive, bits, head_dim,
                                      softcap, scale, token_tile,
                                      _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("scale", "token_tile", "interpret"))
def _mla_paged_attention_jit(q_eff, q_rope, c_pool, r_pool, page_table, pos,
                             alive, scale, token_tile, interpret):
    return mla_paged_attention_pallas(q_eff, q_rope, c_pool, r_pool,
                                      page_table, pos, alive, scale=scale,
                                      token_tile=token_tile,
                                      interpret=interpret)


def mla_paged_attention(q_eff, q_rope, c_pool, r_pool, page_table, pos,
                        alive, *, scale, token_tile=None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Fused absorbed-MLA paged decode → latent context [B,1,H,kv_lora]."""
    return _mla_paged_attention_jit(q_eff, q_rope, c_pool, r_pool,
                                    page_table, pos, alive, scale,
                                    token_tile, _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("bits", "kv_lora", "rope_dim", "scale",
                                    "token_tile", "interpret"))
def _mla_paged_attention_quant_jit(q_eff, q_rope, c_words, r_words, c_cb,
                                   r_cb, page_table, pos, alive, bits,
                                   kv_lora, rope_dim, scale, token_tile,
                                   interpret):
    return mla_paged_attention_quant_pallas(
        q_eff, q_rope, c_words, r_words, c_cb, r_cb, page_table, pos, alive,
        bits=bits, kv_lora=kv_lora, rope_dim=rope_dim, scale=scale,
        token_tile=token_tile, interpret=interpret)


def mla_paged_attention_quant(q_eff, q_rope, c_words, r_words, c_cb, r_cb,
                              page_table, pos, alive, *, bits, kv_lora,
                              rope_dim, scale, token_tile=None,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Absorbed-MLA decode over codebook-quantized latent pages."""
    return _mla_paged_attention_quant_jit(
        q_eff, q_rope, c_words, r_words, c_cb, r_cb, page_table, pos, alive,
        bits, kv_lora, rope_dim, scale, token_tile,
        _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("window", "softcap", "scale", "token_tile",
                                    "interpret"))
def _blockwise_prefill_jit(q, k, v, q_pos, k_pos, window, softcap, scale,
                           token_tile, interpret):
    return blockwise_prefill_pallas(q, k, v, q_pos, k_pos, window=window,
                                    softcap=softcap, scale=scale,
                                    token_tile=token_tile,
                                    interpret=interpret)


def blockwise_prefill(q, k, v, q_pos, k_pos, *, window=None, softcap=None,
                      scale, token_tile,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Chunked-prompt prefill attention: C new queries vs. an S-row K/V
    view, online-softmax per K/V tile (blockwise_prefill.py)."""
    return _blockwise_prefill_jit(q, k, v, q_pos, k_pos, window, softcap,
                                  scale, token_tile,
                                  _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("page_size", "bits", "head_dim", "window",
                                    "softcap", "scale", "token_tile",
                                    "interpret"))
def _blockwise_prefill_quant_jit(q, k_words, v_words, k_cb, v_cb, q_pos,
                                 k_pos, page_size, bits, head_dim, window,
                                 softcap, scale, token_tile,
                                 interpret):
    return blockwise_prefill_quant_pallas(
        q, k_words, v_words, k_cb, v_cb, q_pos, k_pos, page_size=page_size,
        bits=bits, head_dim=head_dim, window=window, softcap=softcap,
        scale=scale, token_tile=token_tile,
        interpret=interpret)


def blockwise_prefill_quant(q, k_words, v_words, k_cb, v_cb, q_pos, k_pos,
                            *, page_size, bits, head_dim, window=None,
                            softcap=None, scale, token_tile,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Chunked-prompt prefill over codebook-quantized KV pages: kv_bits/8
    B per cached scalar of HBM traffic (blockwise_prefill.py)."""
    return _blockwise_prefill_quant_jit(
        q, k_words, v_words, k_cb, v_cb, q_pos, k_pos, page_size, bits,
        head_dim, window, softcap, scale, token_tile,
        _auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _page_gather_jit(pool, page_table, alive, interpret):
    return page_gather_pallas(pool, page_table, alive, interpret=interpret)


def page_gather(pool, page_table, alive,
                interpret: Optional[bool] = None) -> jax.Array:
    """Scalar-prefetch page gather: [P+1, page, ...] pool → per-slot
    logical view [B, max_pages·page, ...] (paged_attention.py)."""
    return _page_gather_jit(pool, page_table, alive,
                            _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("mode", "pow2_c", "scale", "interpret"))
def _fixed_quant_jit(w, mode, pow2_c, scale, interpret):
    return fixed_quant_pallas(w, mode, pow2_c=pow2_c, scale=scale,
                              interpret=interpret)


def fixed_quant(w: jax.Array, mode: str, *, pow2_c: int = 4,
                scale: float = 1.0,
                interpret: Optional[bool] = None) -> jax.Array:
    """Tiled fixed-codebook quantizer (binary | ternary | pow2)."""
    return _fixed_quant_jit(w, mode, pow2_c, float(scale),
                            _auto_interpret(interpret))
