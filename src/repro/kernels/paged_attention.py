"""Pallas TPU kernels: paged-attention decode over the engine's KV pages.

One decode step serves B batch slots, each reading its logical KV
stream through a per-slot page table into a pool of fixed-size pages
(page 0 = trash; see ``models.attention``).  The jnp route materializes
the full gathered view ``[B, max_pages·page, ...]`` in HBM; these
kernels never do:

* the page table / per-slot positions / alive mask ride as
  **scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec`` — the
  ``quantized_gather`` pattern), so the index maps pick the physical
  page of each KV tile and the pages DMA straight into VMEM
  tile-by-tile;
* softmax is **online** (flash-style running max / normalizer in VMEM
  scratch, the ``chunked_attention`` recurrence), so VMEM holds one
  ``token_tile`` of KV at a time regardless of sequence length;
* dead slots' tiles are redirected to the trash page *in the index
  map* — a stalled slot DMAs one repeated page, not ``max_pages``
  arbitrary live ones — and their outputs are fully masked.

The ``*_quant`` variants read **codebook-quantized** pages: uint32
words in the ``pack_rows`` layout plus per-page codebooks
(``core.kvquant``), unpacked shift+mask and LUT-dequantized in VMEM via
``kernels.unpack`` — KV HBM traffic is ``kv_bits/8`` bytes per cached
scalar, the eq.-14 accounting applied to activations.

Grid: ``(B, max_pages · page_size // token_tile)`` — the token axis is
innermost, so the per-slot accumulator scratch carries across KV tiles
and the output block (revisited each step) is written once on the last
tile.  CPU reference route: ``kernels.ref.paged_attention_ref`` family
behind ``dispatch.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kvquant import kv_entries, words_per
from repro.kernels.unpack import dequant_tile, unpack_words_axis1

NEG_INF = -1e30
_EPS = 1e-30


def _softcap(x, cap):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def _page_select(alv, tbl, b, j, tpp):
    """Physical page of KV tile j for slot b; dead slots → trash page."""
    return jnp.where(alv[b] > 0, tbl[b, j // tpp], 0)


def _tile_valid(pos_ref, alive_ref, b, j, bt):
    """[1, bt] bool: token j·bt+t is a live KV entry of slot b."""
    positions = (jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) + j * bt)
    return (positions <= pos_ref[b]) & (alive_ref[b] > 0)


# ---------------------------------------------------------------------------
# GQA (dense and quantized KV pages)


def _gqa_body(q_ref, k, v, o_ref, m_ref, l_ref, acc_ref, *, valid, j,
              nj, softcap, scale):
    """Shared GQA tile step.  k/v: [bt, KV, hd] f32 (already dequant).

    Scratch: m/l [KV, rep], acc [KV, rep, hd] — the flash-softmax
    recurrence of ``chunked_attention``, with ``p`` explicitly masked:
    on a fully-dead tile m stays NEG_INF and exp(NEG_INF - NEG_INF) = 1
    would otherwise inflate the normalizer.
    """
    h, hd = q_ref.shape[1], q_ref.shape[2]
    kv = k.shape[1]
    rep = h // kv
    qg = q_ref[0].reshape(kv, rep, hd).astype(jnp.float32)
    # [KV, rep, bt]: contract hd, batch the kv-head group
    logits = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, softcap)
    ok = jnp.broadcast_to(valid, logits.shape)
    logits = jnp.where(ok, logits, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    p = jnp.where(ok, jnp.exp(logits - m_new[..., None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    # [KV, rep, hd]: contract bt, batch the kv-head group
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    @pl.when(j == nj - 1)
    def _():
        o = acc_ref[...] / jnp.maximum(l_ref[...], _EPS)[..., None]
        o_ref[0] = o.reshape(h, hd)


def _gqa_kernel(tbl_ref, pos_ref, alive_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref, *, bt, nj, softcap, scale):
    del tbl_ref
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    valid = _tile_valid(pos_ref, alive_ref, b, j, bt)
    _gqa_body(q_ref, k_ref[0].astype(jnp.float32),
              v_ref[0].astype(jnp.float32), o_ref, m_ref, l_ref, acc_ref,
              valid=valid, j=j, nj=nj, softcap=softcap, scale=scale)


def _dequant_kv_tile(words, cb, *, head_dim, bits):
    """[bt, KV, Wd] uint32 words + [Gcb, K] codebooks → [bt, KV, hd] f32."""
    bt, kv, wd = words.shape
    idx = unpack_words_axis1(words.reshape(bt * kv, wd), bits)
    idx = idx[:, :head_dim].reshape(bt, kv, head_dim)
    cb = cb.astype(jnp.float32)
    if cb.shape[0] == 1:          # one codebook per page
        vals = dequant_tile(idx.reshape(bt * kv, head_dim), cb)
        return vals.reshape(bt, kv, head_dim)
    heads = [dequant_tile(idx[:, g, :], cb[g:g + 1])
             for g in range(kv)]   # per-kv-head codebooks, KV is static
    return jnp.stack(heads, axis=1)


def _gqa_quant_kernel(tbl_ref, pos_ref, alive_ref, q_ref, kw_ref, vw_ref,
                      kcb_ref, vcb_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      bt, nj, softcap, scale, head_dim, bits):
    del tbl_ref
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    valid = _tile_valid(pos_ref, alive_ref, b, j, bt)
    k = _dequant_kv_tile(kw_ref[0], kcb_ref[0], head_dim=head_dim,
                         bits=bits)
    v = _dequant_kv_tile(vw_ref[0], vcb_ref[0], head_dim=head_dim,
                         bits=bits)
    _gqa_body(q_ref, k, v, o_ref, m_ref, l_ref, acc_ref, valid=valid,
              j=j, nj=nj, softcap=softcap, scale=scale)


def _check_tile(page_size: int, token_tile: int) -> int:
    if token_tile is None:
        token_tile = page_size
    if page_size % token_tile:
        raise ValueError(f"token_tile={token_tile} must divide "
                         f"page_size={page_size}")
    return token_tile


def paged_attention_pallas(q, k_pool, v_pool, page_table, pos, alive, *,
                           softcap=None, scale, token_tile=None,
                           interpret=False):
    """q [B,1,H,hd]; pools [P+1, page, KV, hd] → [B, 1, H·hd] f32."""
    b, _, h, hd = q.shape
    _, page, kv, _ = k_pool.shape
    npg = page_table.shape[1]
    bt = _check_tile(page, token_tile)
    tpp = page // bt
    nj = npg * tpp
    rep = h // kv

    kv_spec = pl.BlockSpec(
        (1, bt, kv, hd),
        lambda b, j, tbl, pos, alv: (_page_select(alv, tbl, b, j, tpp),
                                     j % tpp, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nj),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda b, j, tbl, pos, alv: (b, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda b, j, tbl, pos, alv: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, rep), jnp.float32),
            pltpu.VMEM((kv, rep), jnp.float32),
            pltpu.VMEM((kv, rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, bt=bt, nj=nj, softcap=softcap,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      alive.astype(jnp.int32), q.reshape(b, h, hd), k_pool, v_pool)
    return out.reshape(b, 1, h * hd)


def paged_attention_quant_pallas(q, k_words, v_words, k_cb, v_cb,
                                 page_table, pos, alive, *, bits, head_dim,
                                 softcap=None, scale, token_tile=None,
                                 interpret=False):
    """Quantized-KV paged GQA decode: words [P+1, page, KV, Wd] uint32,
    per-page codebooks [P+1, Gcb, K] → [B, 1, H·hd] f32."""
    b, _, h, hd = q.shape
    _, page, kv, wd = k_words.shape
    if wd != words_per(head_dim, bits):
        raise ValueError(f"word operand width {wd} != "
                         f"ceil({head_dim}/lanes) for kv_bits={bits}")
    npg = page_table.shape[1]
    gcb, k_entries = k_cb.shape[1], k_cb.shape[2]
    if k_entries != kv_entries(bits):
        raise ValueError(f"codebook K={k_entries} != 2**{bits}")
    bt = _check_tile(page, token_tile)
    tpp = page // bt
    nj = npg * tpp
    rep = h // kv

    word_spec = pl.BlockSpec(
        (1, bt, kv, wd),
        lambda b, j, tbl, pos, alv: (_page_select(alv, tbl, b, j, tpp),
                                     j % tpp, 0, 0))
    cb_spec = pl.BlockSpec(
        (1, gcb, k_entries),
        lambda b, j, tbl, pos, alv: (_page_select(alv, tbl, b, j, tpp),
                                     0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nj),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda b, j, tbl, pos, alv: (b, 0, 0)),
            word_spec, word_spec, cb_spec, cb_spec,
        ],
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda b, j, tbl, pos, alv: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, rep), jnp.float32),
            pltpu.VMEM((kv, rep), jnp.float32),
            pltpu.VMEM((kv, rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_quant_kernel, bt=bt, nj=nj, softcap=softcap,
                          scale=scale, head_dim=head_dim, bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      alive.astype(jnp.int32), q.reshape(b, h, hd), k_words, v_words,
      k_cb, v_cb)
    return out.reshape(b, 1, h * hd)


# ---------------------------------------------------------------------------
# MLA (absorbed decode in the latent space; dense and quantized)


def _mla_body(qe_ref, qr_ref, ckv, kr, o_ref, m_ref, l_ref, acc_ref, *,
              valid, j, nj, scale):
    """ckv [bt, L] f32; kr [bt, R] f32.  Accumulates the latent context
    with the same masked flash recurrence as the GQA body (scratch m/l
    [H, 1], acc [H, L])."""
    qe = qe_ref[0].astype(jnp.float32)          # [H, L]
    qr = qr_ref[0].astype(jnp.float32)          # [H, R]
    logits = (jax.lax.dot_general(qe, ckv, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) +
              jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32))
    logits = logits * scale                     # [H, bt]
    ok = jnp.broadcast_to(valid, logits.shape)
    logits = jnp.where(ok, logits, NEG_INF)
    m_prev = m_ref[...]                         # [H, 1]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(logits - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, ckv, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(j == nj - 1)
    def _():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], _EPS)


def _mla_kernel(tbl_ref, pos_ref, alive_ref, qe_ref, qr_ref, c_ref, r_ref,
                o_ref, m_ref, l_ref, acc_ref, *, bt, nj, scale):
    del tbl_ref
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    valid = _tile_valid(pos_ref, alive_ref, b, j, bt)
    _mla_body(qe_ref, qr_ref, c_ref[0].astype(jnp.float32),
              r_ref[0].astype(jnp.float32), o_ref, m_ref, l_ref, acc_ref,
              valid=valid, j=j, nj=nj, scale=scale)


def _dequant_lat_tile(words, cb, *, d, bits):
    """[bt, Wd] uint32 + [1, K] codebook → [bt, d] f32."""
    idx = unpack_words_axis1(words, bits)[:, :d]
    return dequant_tile(idx, cb.astype(jnp.float32))


def _mla_quant_kernel(tbl_ref, pos_ref, alive_ref, qe_ref, qr_ref, cw_ref,
                      rw_ref, ccb_ref, rcb_ref, o_ref, m_ref, l_ref,
                      acc_ref, *, bt, nj, scale, kv_lora, rope_dim, bits):
    del tbl_ref
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    valid = _tile_valid(pos_ref, alive_ref, b, j, bt)
    ckv = _dequant_lat_tile(cw_ref[0], ccb_ref[0], d=kv_lora, bits=bits)
    kr = _dequant_lat_tile(rw_ref[0], rcb_ref[0], d=rope_dim, bits=bits)
    _mla_body(qe_ref, qr_ref, ckv, kr, o_ref, m_ref, l_ref, acc_ref,
              valid=valid, j=j, nj=nj, scale=scale)


def mla_paged_attention_pallas(q_eff, q_rope, c_pool, r_pool, page_table,
                               pos, alive, *, scale, token_tile=None,
                               interpret=False):
    """q_eff [B,1,H,L]; q_rope [B,1,H,R]; pools [P+1, page, L/R]
    → latent context [B, 1, H, L] f32."""
    b, _, h, lat = q_eff.shape
    rd = q_rope.shape[-1]
    _, page, _ = c_pool.shape
    npg = page_table.shape[1]
    bt = _check_tile(page, token_tile)
    tpp = page // bt
    nj = npg * tpp

    def lat_spec(width):
        return pl.BlockSpec(
            (1, bt, width),
            lambda b, j, tbl, pos, alv: (_page_select(alv, tbl, b, j, tpp),
                                         j % tpp, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nj),
        in_specs=[
            pl.BlockSpec((1, h, lat),
                         lambda b, j, tbl, pos, alv: (b, 0, 0)),
            pl.BlockSpec((1, h, rd),
                         lambda b, j, tbl, pos, alv: (b, 0, 0)),
            lat_spec(lat), lat_spec(rd),
        ],
        out_specs=pl.BlockSpec((1, h, lat),
                               lambda b, j, tbl, pos, alv: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, lat), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_mla_kernel, bt=bt, nj=nj, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lat), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      alive.astype(jnp.int32), q_eff.reshape(b, h, lat),
      q_rope.reshape(b, h, rd), c_pool, r_pool)
    return out.reshape(b, 1, h, lat)


def mla_paged_attention_quant_pallas(q_eff, q_rope, c_words, r_words, c_cb,
                                     r_cb, page_table, pos, alive, *, bits,
                                     kv_lora, rope_dim, scale,
                                     token_tile=None, interpret=False):
    """Quantized latent pages: words [P+1, page, W*] uint32 + per-page
    codebooks [P+1, 1, K] → latent context [B, 1, H, L] f32."""
    b, _, h, lat = q_eff.shape
    rd = q_rope.shape[-1]
    _, page, cwd = c_words.shape
    rwd = r_words.shape[-1]
    if cwd != words_per(kv_lora, bits) or rwd != words_per(rope_dim, bits):
        raise ValueError(f"latent word widths ({cwd},{rwd}) don't match "
                         f"kv_bits={bits} for dims ({kv_lora},{rope_dim})")
    k_entries = kv_entries(bits)
    npg = page_table.shape[1]
    bt = _check_tile(page, token_tile)
    tpp = page // bt
    nj = npg * tpp

    def word_spec(width):
        return pl.BlockSpec(
            (1, bt, width),
            lambda b, j, tbl, pos, alv: (_page_select(alv, tbl, b, j, tpp),
                                         j % tpp, 0))

    cb_spec = pl.BlockSpec(
        (1, 1, k_entries),
        lambda b, j, tbl, pos, alv: (_page_select(alv, tbl, b, j, tpp),
                                     0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nj),
        in_specs=[
            pl.BlockSpec((1, h, lat),
                         lambda b, j, tbl, pos, alv: (b, 0, 0)),
            pl.BlockSpec((1, h, rd),
                         lambda b, j, tbl, pos, alv: (b, 0, 0)),
            word_spec(cwd), word_spec(rwd), cb_spec, cb_spec,
        ],
        out_specs=pl.BlockSpec((1, h, lat),
                               lambda b, j, tbl, pos, alv: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, lat), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_mla_quant_kernel, bt=bt, nj=nj, scale=scale,
                          kv_lora=kv_lora, rope_dim=rope_dim, bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lat), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      alive.astype(jnp.int32), q_eff.reshape(b, h, lat),
      q_rope.reshape(b, h, rd), c_words, r_words, c_cb, r_cb)
    return out.reshape(b, 1, h, lat)


# ---------------------------------------------------------------------------
# Standalone page gather (the fused kernels make this a fallback / debug
# view; it also feeds the bench row that prices the gather alone)


def _page_gather_kernel(tbl_ref, alive_ref, p_ref, o_ref):
    del tbl_ref, alive_ref
    o_ref[...] = p_ref[...]


def page_gather_pallas(pool, page_table, alive, *, interpret=False):
    """[P+1, page, ...] pool → [B, max_pages·page, ...] logical view,
    one page DMA per (slot, logical page); dead slots read the trash
    page (the ``gather_pages_ref`` alive-masking contract)."""
    b, npg = page_table.shape
    page = pool.shape[1]
    feat = pool.shape[2:]
    d = 1
    for f in feat:
        d *= f
    pool2 = pool.reshape(pool.shape[0], page, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, npg),
        in_specs=[
            pl.BlockSpec(
                (1, page, d),
                lambda b, j, tbl, alv: (jnp.where(alv[b] > 0, tbl[b, j], 0),
                                        0, 0)),
        ],
        out_specs=pl.BlockSpec((1, page, d),
                               lambda b, j, tbl, alv: (b, j, 0)),
    )
    out = pl.pallas_call(
        _page_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, npg * page, d), pool.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), alive.astype(jnp.int32), pool2)
    return out.reshape((b, npg * page) + feat)
