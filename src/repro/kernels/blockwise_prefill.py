"""Pallas TPU kernels: blockwise (chunked-prompt) prefill attention.

One prompt chunk of C query tokens attends over a stored K/V view of S
rows — the engine's page-gathered slot view, or the one-shot oracle's
growing prefill buffer.  The jnp route (``kernels.ref.
blockwise_prefill_ref``) materializes full [C, S] score tiles per scan
step in f32; this kernel never does:

* softmax is **online** (flash-style running max / normalizer in VMEM
  scratch — the same recurrence as ``kernels.paged_attention``), so
  VMEM holds one ``token_tile`` of K/V at a time regardless of S: the
  prefill VMEM footprint is flat in prompt length;
* visibility is position-derived: a view row with ``k_pos > q_pos``
  (future tokens, another tenant's stale ring rows, the dispatch
  route's ``POS_SENTINEL`` padding) masks to exact +0 probability, so
  trailing all-masked tiles are bitwise no-ops — the property that
  keeps engine (fixed-capacity view) and oracle (growing view) streams
  bit-equal;
* the ``_quant`` variant reads **codebook-quantized** pages: uint32
  words in the ``pack_rows`` layout plus per-page codebooks
  (``core.kvquant``), unpacked shift+mask and LUT-dequantized in VMEM
  via ``kernels.unpack`` — K/V HBM traffic at ``kv_bits/8`` bytes per
  cached scalar on the one remaining dense-compute path.

Grid: ``(B, S // token_tile)`` — the KV-tile axis is innermost so the
per-chunk accumulator scratch carries across tiles and the output block
is written once on the last tile.  Routed + block-autotuned through
``dispatch.blockwise_prefill_attention[_quant]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kvquant import kv_entries, words_per
from repro.kernels.paged_attention import _dequant_kv_tile

NEG_INF = -1e30
_EPS = 1e-30


def _softcap(x, cap):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def _positions(q_pos, k_pos, bt):
    """Position operands in (8, 128)-legal block shapes: q_pos as one
    [C, 1] column, k_pos as [S/bt, 1, bt] so a KV tile's positions are
    one whole trailing [1, bt] block."""
    return (q_pos.astype(jnp.int32).reshape(-1, 1),
            k_pos.astype(jnp.int32).reshape(-1, 1, bt))


def _prefill_body(q_ref, k, v, qp_ref, kp_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, j, nj, window, softcap, scale):
    """Shared tile step.  k/v: [bt, KV, hd/vd] f32 (already dequant).

    Scratch: m/l [KV, rep, C], acc [KV, rep, C, vd] — the masked flash
    recurrence of ``ref.blockwise_prefill_ref``, tile-for-tile.
    """
    c, h, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    kv, vd = k.shape[1], v.shape[2]
    rep = h // kv
    qg = q_ref[0].astype(jnp.float32).reshape(c, kv, rep, hd)
    qg = qg.transpose(1, 2, 0, 3)                    # [KV, rep, C, hd]
    kt = k.transpose(1, 0, 2)                        # [KV, bt, hd]
    vt = v.transpose(1, 0, 2)                        # [KV, bt, vd]
    # [KV, rep, C, bt]: contract hd, batch the kv-head group
    logits = jax.lax.dot_general(
        qg, kt, (((3,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, softcap)
    qpos = qp_ref[...]                               # [C, 1]
    kpos = kp_ref[0]                                 # [1, bt]
    ok = kpos <= qpos
    if window is not None:
        ok &= (qpos - kpos) < window
    ok = jnp.broadcast_to(ok[None, None, :, :], logits.shape)
    logits = jnp.where(ok, logits, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    p = jnp.where(ok, jnp.exp(logits - m_new[..., None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    # [KV, rep, C, vd]: contract bt, batch the kv-head group
    pv = jax.lax.dot_general(
        p, vt, (((3,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    @pl.when(j == nj - 1)
    def _():
        o = acc_ref[...] / jnp.maximum(l_ref[...], _EPS)[..., None]
        o_ref[0] = o.transpose(2, 0, 1, 3).reshape(c, h, vd)


def _prefill_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, m_ref,
                    l_ref, acc_ref, *, nj, window, softcap, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    _prefill_body(q_ref, k_ref[0].astype(jnp.float32),
                  v_ref[0].astype(jnp.float32), qp_ref, kp_ref, o_ref,
                  m_ref, l_ref, acc_ref, j=j, nj=nj, window=window,
                  softcap=softcap, scale=scale)


def _prefill_quant_kernel(q_ref, kw_ref, vw_ref, kcb_ref, vcb_ref, qp_ref,
                          kp_ref, o_ref, m_ref, l_ref, acc_ref, *, nj,
                          window, softcap, scale, head_dim, bits):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    k = _dequant_kv_tile(kw_ref[0], kcb_ref[0, 0], head_dim=head_dim,
                         bits=bits)
    v = _dequant_kv_tile(vw_ref[0], vcb_ref[0, 0], head_dim=head_dim,
                         bits=bits)
    _prefill_body(q_ref, k, v, qp_ref, kp_ref, o_ref, m_ref, l_ref,
                  acc_ref, j=j, nj=nj, window=window, softcap=softcap,
                  scale=scale)


def blockwise_prefill_pallas(q, k, v, q_pos, k_pos, *, window=None,
                             softcap=None, scale, token_tile,
                             interpret=False):
    """q [B,C,H,hd]; k [B,S,KV,hd]; v [B,S,KV,vd]; q_pos [C]; k_pos [S]
    int32 (S a multiple of ``token_tile``; padded rows carry the
    sentinel position) → [B, C, H, vd] f32."""
    b, c, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    if s % token_tile:
        raise ValueError(f"view rows {s} not a multiple of "
                         f"token_tile={token_tile}")
    nj = s // token_tile
    rep = h // kv
    bt = token_tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, nj),
        in_specs=[
            pl.BlockSpec((1, c, h, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, bt, kv, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bt, kv, vd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((c, 1), lambda b, j: (0, 0)),
            pl.BlockSpec((1, 1, bt), lambda b, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, h, vd), lambda b, j: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, rep, c), jnp.float32),
            pltpu.VMEM((kv, rep, c), jnp.float32),
            pltpu.VMEM((kv, rep, c, vd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, nj=nj, window=window,
                          softcap=softcap, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, vd), jnp.float32),
        interpret=interpret,
    )(q, k, v, *_positions(q_pos, k_pos, bt))
    return out


def blockwise_prefill_quant_pallas(q, k_words, v_words, k_cb, v_cb, q_pos,
                                   k_pos, *, page_size, bits, head_dim,
                                   window=None, softcap=None, scale,
                                   token_tile, interpret=False):
    """Quantized-page view: words [B, S, KV, Wd] uint32 (S = pages·page,
    logical row order) + per-page codebooks [B, npg, Gcb, K]
    → [B, C, H, hd] f32.  ``token_tile`` must divide ``page_size`` so a
    K/V tile's codebook is a single page's."""
    b, c, h, hd = q.shape
    s, kv, wd = k_words.shape[1], k_words.shape[2], k_words.shape[3]
    if wd != words_per(head_dim, bits):
        raise ValueError(f"word operand width {wd} != "
                         f"ceil({head_dim}/lanes) for kv_bits={bits}")
    gcb, k_ent = k_cb.shape[2], k_cb.shape[3]
    if k_ent != kv_entries(bits):
        raise ValueError(f"codebook K={k_ent} != 2**{bits}")
    if page_size % token_tile or s % page_size:
        raise ValueError(f"token_tile={token_tile} must divide "
                         f"page_size={page_size} (view rows {s})")
    nj = s // token_tile
    tpp = page_size // token_tile
    rep = h // kv
    bt = token_tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, nj),
        in_specs=[
            pl.BlockSpec((1, c, h, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, bt, kv, wd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bt, kv, wd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, gcb, k_ent),
                         lambda b, j: (b, j // tpp, 0, 0)),
            pl.BlockSpec((1, 1, gcb, k_ent),
                         lambda b, j: (b, j // tpp, 0, 0)),
            pl.BlockSpec((c, 1), lambda b, j: (0, 0)),
            pl.BlockSpec((1, 1, bt), lambda b, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, h, hd), lambda b, j: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, rep, c), jnp.float32),
            pltpu.VMEM((kv, rep, c), jnp.float32),
            pltpu.VMEM((kv, rep, c, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_quant_kernel, nj=nj, window=window,
                          softcap=softcap, scale=scale, head_dim=head_dim,
                          bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, hd), jnp.float32),
        interpret=interpret,
    )(q, k_words, v_words, k_cb, v_cb, *_positions(q_pos, k_pos, bt))
    return out
