"""Attention: chunked (flash-style) training path + KV-cache decode paths.

* ``chunked_attention`` — pure-jnp online-softmax attention over KV chunks
  (memory O(S·chunk) instead of O(S²)); supports GQA head broadcasting,
  causal masking, sliding windows (banded compute: local layers only touch
  the ``window + q_chunk`` KV band ⇒ O(S·W) FLOPs, not O(S²)), and
  Gemma-2-style attention-logit softcap.
* GQA with full or ring-buffer (sliding-window) caches for decode.
* MLA (DeepSeek-V2 multi-head latent attention): trains on the expanded
  K/V; decodes in the *compressed* space via the matrix-absorption trick,
  so the cache is [S, kv_lora + rope_dim] per token regardless of heads.

Causal-waste note (roofline): the global-attention training path scans all
KV chunks per query chunk and masks the upper triangle ⇒ HLO FLOPs ≈ 2×
useful attention FLOPs.  This shows up honestly in the MODEL_FLOPS /
HLO_FLOPs ratio and is one of the §Perf hillclimb levers (banded/triangle
scheduling).  Local (windowed) layers already avoid it.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kvquant
from repro.kernels import dispatch
from repro.models.layers import apply_rope, softcap
from repro.models.qleaf import qmatmul, qweight
from repro.models.sharding_ctx import active_mesh, constrain

Array = jax.Array
NEG_INF = -1e30


def _mask_bias(q_pos: Array, k_pos: Array, window: Optional[int]) -> Array:
    """[Sq, Sk] additive bias: causal (+ sliding window if given)."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF)


def chunked_attention(
    q: Array, k: Array, v: Array,
    q_positions: Array, k_positions: Array,
    *,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
    causal_unroll: bool = False,
) -> Array:
    """q: [B,Sq,H,hd]; k,v: [B,Sk,KV,hd]; positions: [Sq],[Sk] (global ids).

    Returns [B,Sq,H,hd].  GQA: H must be a multiple of KV.
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    vd = v.shape[-1]                       # value dim may differ (MLA)
    rep = h // kv
    scale = scale if scale is not None else hd ** -0.5
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    nq, nk = sq // qc, sk // kc
    assert nq * qc == sq and nk * kc == sk, (sq, sk, qc, kc)

    # [nq, B, qc, H, hd]
    qs = q.reshape(b, nq, qc, h, hd).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nq, qc)

    if window is not None and sk > kc:
        return _banded_attention(qs, qp, k, v, k_positions, window, rep,
                                 scale, attn_softcap, qc, kc, b, h, hd, vd, sq)

    if causal_unroll and sq == sk and nq <= 8:
        return _triangular_attention(qs, qp, k, v, k_positions, rep, scale,
                                     attn_softcap, qc, kc, b, h, hd, vd, sq,
                                     window)

    ks = k.reshape(b, nk, kc, kv, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kc, kv, vd).transpose(1, 0, 2, 3, 4)
    kp = k_positions.reshape(nk, kc)

    def q_body(_, qblk):
        qi, qpos = qblk                                   # [B,qc,H,hd], [qc]

        def kv_body(carry, kblk):
            m, l, o = carry
            ki, vi, kpos = kblk
            # logits [B, KV, rep, qc, kc]
            qg = qi.reshape(b, qc, kv, rep, hd)
            logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, ki,
                                preferred_element_type=jnp.float32) * scale
            logits = softcap(logits, attn_softcap)
            logits = logits + _mask_bias(qpos, kpos, window)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkrqs,bskd->bkrqd", p.astype(vi.dtype), vi)
            o = o * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l, o), None

        m0 = jnp.full((b, kv, rep, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, rep, qc), jnp.float32)
        o0 = jnp.zeros((b, kv, rep, qc, vd), jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_body, (m0, l0, o0), (ks, vs, kp))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        # [B,KV,rep,qc,vd] -> [B,qc,H,vd]
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, vd)
        return None, o.astype(qi.dtype)

    _, outs = jax.lax.scan(q_body, None, (qs, qp))        # [nq,B,qc,H,vd]
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, vd)


def _triangular_attention(qs, qp, k, v, k_positions, rep, scale,
                          attn_softcap, qc, kc, b, h, hd, vd, sq, window):
    """Causal attention with a statically-unrolled triangular schedule:
    q chunk i attends only k[: (i+1)·qc] — no fully-masked blocks are ever
    computed (the scan path burns ~2× attention FLOPs on them).  Used when
    nq ≤ 8 so the unrolled HLO stays small (§Perf qwen iteration 3)."""
    kv = k.shape[2]
    nq = qs.shape[0]
    outs = []
    for i in range(nq):
        end = (i + 1) * qc
        qi, qpos = qs[i], qp[i]
        ki, vi = k[:, :end], v[:, :end]
        kpos = k_positions[:end]
        qg = qi.reshape(b, qc, kv, rep, hd)
        logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, ki,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, attn_softcap)
        logits = logits + _mask_bias(qpos, kpos, window)
        m = logits.max(axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        o = jnp.einsum("bkrqs,bskd->bkrqd", p.astype(vi.dtype), vi)
        o = o / p.sum(axis=-1, keepdims=True).astype(o.dtype)
        outs.append(o.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, vd))
    return jnp.concatenate(outs, axis=1).astype(qs.dtype)


def _banded_attention(qs, qp, k, v, k_positions, window, rep, scale,
                      attn_softcap, qc, kc, b, h, hd, vd, sq):
    """Sliding-window path: each q chunk reads only its KV band."""
    kv = k.shape[2]
    sk = k.shape[1]
    band = ((window + qc - 1) // kc + 1) * kc             # static band length
    band = min(band + kc, sk)                             # cover chunk offset
    nq = qs.shape[0]

    def q_body(_, xs):
        qi, qpos, idx = xs
        q_start = idx * qc
        start = jnp.clip(q_start + qc - band, 0, sk - band)
        ki = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        kpos = jax.lax.dynamic_slice_in_dim(k_positions, start, band, axis=0)
        qg = qi.reshape(b, qc, kv, rep, hd)
        logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, ki,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, attn_softcap)
        logits = logits + _mask_bias(qpos, kpos, window)
        m = logits.max(axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        o = jnp.einsum("bkrqs,bskd->bkrqd", p.astype(vi.dtype), vi)
        o = o / p.sum(axis=-1, keepdims=True).astype(o.dtype)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, vd)
        return None, o.astype(qi.dtype)

    idxs = jnp.arange(nq)
    _, outs = jax.lax.scan(q_body, None, (qs, qp, idxs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, vd)


# ---------------------------------------------------------------------------
# GQA block (params + train / prefill / decode)
# ---------------------------------------------------------------------------

def init_gqa(key, d_model, n_heads, n_kv, head_dim, qkv_bias=False,
             dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d_model, n_heads * head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, n_kv * head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, n_kv * head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_heads * head_dim, d_model))
               * (n_heads * head_dim) ** -0.5).astype(dtype),
    }
    if qkv_bias:
        p["q_bias"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["k_bias"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["v_bias"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def _qkv(p, x, n_heads, n_kv, head_dim):
    """q/k/v projections; each weight may be dense or a quantized leaf
    (``serving_params`` layouts) — qleaf routes to the codebook-matmul
    kernels in that case."""
    b, s, _ = x.shape
    q = qmatmul(p, "wq", x)
    k = qmatmul(p, "wk", x)
    v = qmatmul(p, "wv", x)
    if "q_bias" in p:
        q, k, v = q + p["q_bias"], k + p["k_bias"], v + p["v_bias"]
    q = constrain(q.reshape(b, s, n_heads, head_dim),
                  "batch", None, "heads", None)
    k = constrain(k.reshape(b, s, n_kv, head_dim),
                  "batch", None, "kv_heads", None)
    v = constrain(v.reshape(b, s, n_kv, head_dim),
                  "batch", None, "kv_heads", None)
    return q, k, v


def gqa_forward(p, x, positions, *, n_heads, n_kv, head_dim,
                window=None, attn_softcap=None, rope_theta=10000.0,
                q_chunk=1024, kv_chunk=1024, query_scale=None,
                causal_unroll=False):
    """Full-sequence causal forward (training / prefill)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    q = apply_rope(q, positions[None, :], rope_theta)
    k = apply_rope(k, positions[None, :], rope_theta)
    o = chunked_attention(q, k, v, positions, positions, window=window,
                          attn_softcap=attn_softcap, q_chunk=q_chunk,
                          kv_chunk=kv_chunk, scale=query_scale,
                          causal_unroll=causal_unroll)
    return qmatmul(p, "wo", o.reshape(b, s, n_heads * head_dim)), (k, v)


class KVCache(NamedTuple):
    k: Array          # [B, C, KV, hd]  (C = max_len or window)
    v: Array


def init_kv_cache(batch, capacity, n_kv, head_dim, dtype):
    z = jnp.zeros((batch, capacity, n_kv, head_dim), dtype)
    return KVCache(k=z, v=z)


def gqa_decode(p, x_t, cache: KVCache, pos, *, n_heads, n_kv, head_dim,
               ring=False, window=None, attn_softcap=None,
               rope_theta=10000.0, query_scale=None):
    """One-token decode. x_t: [B,1,D]; pos: scalar position index.

    ``ring`` (static, from the layer kind) marks a sliding-window ring
    buffer of capacity = window.
    """
    b = x_t.shape[0]
    q, k, v = _qkv(p, x_t, n_heads, n_kv, head_dim)
    pos_arr = jnp.asarray(pos)[None]
    q = apply_rope(q, pos_arr[None, :], rope_theta)
    k = apply_rope(k, pos_arr[None, :], rope_theta)

    cap = cache.k.shape[1]
    slot = (pos % cap) if ring else pos
    ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), slot, axis=1)

    idx = jnp.arange(cap)
    if ring:
        # slot i holds position p_i = pos - ((pos - i) mod cap)
        slot_pos = pos - jnp.mod(pos - idx, cap)
        valid = (slot_pos >= 0) & (slot_pos > pos - (window or cap))
    else:
        slot_pos = idx
        valid = idx <= pos
        if window is not None:
            valid &= idx > pos - window
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    rep = n_heads // n_kv
    qg = q.reshape(b, 1, n_kv, rep, head_dim)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, attn_softcap)
    logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
    attn = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkrqs,bskd->bkrqd", attn.astype(cv.dtype), cv)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, 1, n_heads * head_dim)
    return qmatmul(p, "wo", o), KVCache(k=ck, v=cv)


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------

def init_mla(key, d_model, n_heads, *, kv_lora, rope_dim, nope_dim, v_dim,
             dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    s = d_model ** -0.5
    qdim = n_heads * (nope_dim + rope_dim)
    return {
        "wq": (jax.random.normal(ks[0], (d_model, qdim)) * s).astype(dtype),
        "w_dkv": (jax.random.normal(ks[1], (d_model, kv_lora + rope_dim)) * s).astype(dtype),
        "w_uk": (jax.random.normal(ks[2], (kv_lora, n_heads * nope_dim))
                 * kv_lora ** -0.5).astype(dtype),
        "w_uv": (jax.random.normal(ks[3], (kv_lora, n_heads * v_dim))
                 * kv_lora ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[4], (n_heads * v_dim, d_model))
               * (n_heads * v_dim) ** -0.5).astype(dtype),
        "kv_norm_scale": jnp.zeros((kv_lora,), dtype),
    }


def _mla_q(p, x, n_heads, nope_dim, rope_dim, positions, rope_theta):
    """positions: broadcastable against [B, S] (e.g. [1, S] for the full
    forward, [B, 1] for a per-slot decode step)."""
    b, s, _ = x.shape
    q = qmatmul(p, "wq", x).reshape(b, s, n_heads, nope_dim + rope_dim)
    q_nope, q_rope = q[..., :nope_dim], q[..., nope_dim:]
    q_rope = apply_rope(q_rope, positions, rope_theta)
    return q_nope, q_rope


def mla_forward(p, x, positions, *, n_heads, kv_lora, rope_dim, nope_dim,
                v_dim, rope_theta=10000.0, q_chunk=1024, kv_chunk=1024):
    """Training/prefill: expand the latent KV and run standard attention."""
    from repro.models.layers import rms_norm
    b, s, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, n_heads, nope_dim, rope_dim,
                            positions[None, :], rope_theta)
    q_nope = constrain(q_nope, "batch", None, "heads", None)
    q_rope = constrain(q_rope, "batch", None, "heads", None)
    dkv = qmatmul(p, "w_dkv", x)
    c_kv = rms_norm(dkv[..., :kv_lora], p["kv_norm_scale"])
    k_rope = apply_rope(dkv[..., None, kv_lora:], positions[None, :], rope_theta)
    k_nope = constrain(
        qmatmul(p, "w_uk", c_kv).reshape(b, s, n_heads, nope_dim),
        "batch", None, "heads", None)
    v = constrain(qmatmul(p, "w_uv", c_kv).reshape(b, s, n_heads, v_dim),
                  "batch", None, "heads", None)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, n_heads, rope_dim))],
                        axis=-1)
    scale = (nope_dim + rope_dim) ** -0.5
    o = chunked_attention(q, k, v, positions, positions, q_chunk=q_chunk,
                          kv_chunk=kv_chunk, scale=scale)
    cache = {"c_kv": c_kv, "k_rope": k_rope[..., 0, :]}
    return qmatmul(p, "wo", o.reshape(b, s, n_heads * v_dim)), cache


class MLACache(NamedTuple):
    c_kv: Array      # [B, C, kv_lora]
    k_rope: Array    # [B, C, rope_dim]


def init_mla_cache(batch, capacity, kv_lora, rope_dim, dtype):
    return MLACache(c_kv=jnp.zeros((batch, capacity, kv_lora), dtype),
                    k_rope=jnp.zeros((batch, capacity, rope_dim), dtype))


def mla_decode(p, x_t, cache: MLACache, pos, *, n_heads, kv_lora, rope_dim,
               nope_dim, v_dim, rope_theta=10000.0):
    """Absorbed decode: attention entirely in the [kv_lora] latent space.

    q_eff = q_nope · W_UK   (per head: [nope]·[nope,kv_lora])
    logits = q_eff·c_kv + q_rope·k_rope ;  ctx = attn·c_kv ;
    out_head = ctx · W_UV.  Cache traffic per token: kv_lora + rope_dim.
    """
    from repro.models.layers import rms_norm
    b = x_t.shape[0]
    pos_arr = jnp.asarray(pos)[None]
    q_nope, q_rope = _mla_q(p, x_t, n_heads, nope_dim, rope_dim,
                            pos_arr[None, :], rope_theta)
    dkv = qmatmul(p, "w_dkv", x_t)
    c_kv_t = rms_norm(dkv[..., :kv_lora], p["kv_norm_scale"])
    k_rope_t = apply_rope(dkv[..., None, kv_lora:], pos_arr[None, :], rope_theta)[:, :, 0]

    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache.c_kv, c_kv_t.astype(cache.c_kv.dtype), pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache.k_rope, k_rope_t.astype(cache.k_rope.dtype), pos, axis=1)

    # Absorbed factors are einsum operands: fetch dense via qweight (an
    # in-jit dequant temporary when the leaf is quantized) and reshape.
    w_uk = qweight(p, "w_uk").reshape(kv_lora, n_heads, nope_dim)
    q_eff = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_uk)        # [B,1,H,kv_lora]
    logits = (jnp.einsum("bqhl,bsl->bhqs", q_eff, ckv) +
              jnp.einsum("bqhd,bsd->bhqs", q_rope, krope))
    logits = logits.astype(jnp.float32) * (nope_dim + rope_dim) ** -0.5
    cap = ckv.shape[1]
    valid = jnp.arange(cap) <= pos
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    attn = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqs,bsl->bqhl", attn.astype(ckv.dtype), ckv)
    w_uv = qweight(p, "w_uv").reshape(kv_lora, n_heads, v_dim)
    o = jnp.einsum("bqhl,lhd->bqhd", ctx, w_uv).reshape(b, 1, n_heads * v_dim)
    return qmatmul(p, "wo", o), MLACache(c_kv=ckv, k_rope=krope)


# ---------------------------------------------------------------------------
# Paged / slot-aware decode (continuous-batching engine)
#
# Global-attention layers store KV in a pool of fixed-size pages shared by
# all batch slots; a per-slot page table maps logical position t to
# physical cell (table[slot, t // page] , t % page).  Physical page 0 is
# reserved as the trash page: dead slots (and unallocated logical pages)
# point at it, so one fused decode step serves any admission/eviction
# state without shape changes or recompiles.  Sliding-window layers keep
# their constant-size per-slot ring buffer instead (capacity == window).
# ---------------------------------------------------------------------------


class PagedKVCache(NamedTuple):
    k: Array          # [n_pages + 1, page, KV·hd]  (page 0 = trash),
    v: Array          # stacked [G, ...] over a stack's layers


class PagedMLACache(NamedTuple):
    c_kv: Array       # [n_pages + 1, page, kv_lora]
    k_rope: Array     # [n_pages + 1, page, rope_dim]


def init_paged_kv_cache(n_pages, page_size, n_kv, head_dim, dtype):
    """Dense pages stored lane-dense: a token's kv heads side by side,
    the layout the paged-attention kernel reads with no padding."""
    shape = (n_pages + 1, page_size, n_kv * head_dim)
    return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def init_paged_mla_cache(n_pages, page_size, kv_lora, rope_dim, dtype):
    return PagedMLACache(
        c_kv=jnp.zeros((n_pages + 1, page_size, kv_lora), dtype),
        k_rope=jnp.zeros((n_pages + 1, page_size, rope_dim), dtype))


def _slot_cells(page_table: Array, pos: Array, alive: Array,
                page_size: int):
    """(physical page, in-page offset) [B] each slot writes at ``pos``;
    dead (or page-starved) slots write the trash page."""
    b = pos.shape[0]
    npg = page_table.shape[1]
    pg = jnp.clip(pos // page_size, 0, npg - 1)
    phys = page_table[jnp.arange(b), pg]
    return jnp.where(alive, phys, 0), pos % page_size


def _write_slot(pool: Array, page_table: Array, pos: Array, alive: Array,
                new: Array, page_size: int) -> Array:
    """Scatter one new entry per slot into its current page.

    pool [P+1, page, ...]; page_table [B, max_pages]; pos/alive [B];
    new [B, ...].  Dead (or page-starved) slots write the trash page.
    """
    phys, off = _slot_cells(page_table, pos, alive, page_size)
    return pool.at[phys, off].set(new.astype(pool.dtype), mode="drop")


def _gather_slots(pool: Array, page_table: Array, alive: Array,
                  heads: int = 0) -> Array:
    """Logical KV view per slot: [B, max_pages·page, ...].

    Dead slots' table rows are masked to the trash page *before* the
    gather (dispatch routes to ``kernels.ref.gather_pages_ref`` on CPU or
    the scalar-prefetch Pallas gather on TPU), so a stalled/empty slot
    contributes one repeated trash page instead of ``max_pages``
    arbitrary live pages to the gather footprint.  ``heads``: the kv
    heads a lane-dense pool holds (``dispatch.page_gather``).
    """
    return dispatch.page_gather(pool, page_table, alive, heads=heads,
                                mesh=active_mesh())


def _slot_attention(q, ck, cv, valid, *, n_heads, n_kv, head_dim,
                    attn_softcap, scale):
    """Masked decode attention over per-slot gathered KV.

    q [B,1,H,hd]; ck/cv [B,cap,KV,hd]; valid [B,cap] bool."""
    b = q.shape[0]
    rep = n_heads // n_kv
    qg = q.reshape(b, 1, n_kv, rep, head_dim)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, attn_softcap)
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    attn = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkrqs,bskd->bkrqd", attn.astype(cv.dtype), cv)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, 1, n_heads * head_dim)


def gqa_decode_paged(p, x_t, cache: PagedKVCache, layer, page_table, pos,
                     alive, *, n_heads, n_kv, head_dim, page_size,
                     attn_softcap=None, rope_theta=10000.0,
                     query_scale=None):
    """One-token GQA decode for a batch of engine slots, at ``layer`` of
    a stack's pools.

    x_t [B,1,D]; ``cache`` the stack's pools [G, P+1, page, KV·hd], which
    the decode program carries through its layer loop and updates in
    place; page_table [B, max_pages] int32; pos [B] int32 per-slot write
    positions; alive [B] bool (dead slots: reads fully masked, writes
    land on the trash page).
    """
    b = x_t.shape[0]
    q, k, v = _qkv(p, x_t, n_heads, n_kv, head_dim)
    posb = pos[:, None]
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)

    phys, off = _slot_cells(page_table, pos, alive, page_size)

    def write(pool, new):
        return pool.at[layer, phys, off].set(
            new.reshape(b, -1).astype(pool.dtype), mode="drop")

    ck, cv = write(cache.k, k), write(cache.v, v)
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    # fused page-gather + online-softmax decode; the CPU ref route is the
    # verbatim former _gather_slots/_slot_attention math (bit-identical)
    o = dispatch.paged_attention(q, ck, cv, layer, page_table, pos, alive,
                                 softcap=attn_softcap, scale=scale,
                                 mesh=active_mesh())
    return qmatmul(p, "wo", o), PagedKVCache(k=ck, v=cv)


# --- codebook-quantized paged KV (kv_bits ∈ {2,4,8}) -----------------------
#
# Pages store bit-packed codebook indices (``core.kvquant`` pack_rows
# layout) plus per-page codebooks fit at write time.  Freeze-on-first-
# write: the codebook of a page is fit exactly once — by the prefill
# commit (over the whole zero-padded page) or by the decode step that
# writes the page's first cell — and every later in-page write assigns
# against the frozen codebook.  Storage is therefore a pure function of
# the written values (replay/restore deterministic), and the stored
# dequantized value equals ``cb[assign(v, cb)]`` exactly.


class QuantPagedKVCache(NamedTuple):
    k_words: Array    # [n_pages + 1, page, KV, Wd] uint32 packed indices
    v_words: Array
    k_cb: Array       # [n_pages + 1, Gcb, K]; Gcb = n_kv ("head") | 1 ("page")
    v_cb: Array


class QuantPagedMLACache(NamedTuple):
    c_words: Array    # [n_pages + 1, page, ⌈kv_lora/lanes⌉] uint32
    r_words: Array    # [n_pages + 1, page, ⌈rope_dim/lanes⌉] uint32
    c_cb: Array       # [n_pages + 1, 1, K]  (latent pages: per-page cbs)
    r_cb: Array


def init_quant_paged_kv_cache(n_pages, page_size, n_kv, head_dim, bits,
                              cb_mode, dtype):
    wd = kvquant.words_per(head_dim, kvquant.check_kv_bits(bits))
    gcb = n_kv if cb_mode == "head" else 1
    zw = jnp.zeros((n_pages + 1, page_size, n_kv, wd), jnp.uint32)
    zc = jnp.zeros((n_pages + 1, gcb, kvquant.kv_entries(bits)), dtype)
    return QuantPagedKVCache(k_words=zw, v_words=zw, k_cb=zc, v_cb=zc)


def init_quant_paged_mla_cache(n_pages, page_size, kv_lora, rope_dim, bits,
                               dtype):
    k = kvquant.kv_entries(kvquant.check_kv_bits(bits))
    return QuantPagedMLACache(
        c_words=jnp.zeros(
            (n_pages + 1, page_size, kvquant.words_per(kv_lora, bits)),
            jnp.uint32),
        r_words=jnp.zeros(
            (n_pages + 1, page_size, kvquant.words_per(rope_dim, bits)),
            jnp.uint32),
        c_cb=jnp.zeros((n_pages + 1, 1, k), dtype),
        r_cb=jnp.zeros((n_pages + 1, 1, k), dtype))


def _quant_groups(new: Array, cb_mode: str) -> Array:
    """Reshape one token's write row to [B, Gcb, N] codebook groups."""
    if new.ndim == 2:                  # MLA latent/rope row: per-page cb
        return new[:, None, :]
    b, kv, hd = new.shape
    if cb_mode == "head":
        return new                     # one cb per kv head
    return new.reshape(b, 1, kv * hd)  # one cb per page


def _write_slot_quant(words: Array, cbs: Array, page_table: Array,
                      pos: Array, alive: Array, new: Array, page_size: int,
                      bits: int, cb_mode: str):
    """Quantizing twin of ``_write_slot``: fit-or-reuse the page codebook,
    assign, bit-pack, scatter.

    words [P+1, page, (KV,) Wd]; cbs [P+1, Gcb, K]; new [B, (KV,) d].
    A slot writing offset 0 of a page fits that page's codebook from its
    token row and freezes it; offsets > 0 assign against the frozen one.
    Dead slots write the trash page (page 0) and never refit its cb.
    """
    b = new.shape[0]
    npg = page_table.shape[1]
    pg = jnp.clip(pos // page_size, 0, npg - 1)
    phys = page_table[jnp.arange(b), pg]
    phys = jnp.where(alive, phys, 0)
    off = pos % page_size
    is_first = (off == 0) & alive

    grp = _quant_groups(new, cb_mode)              # [B, Gcb, N]
    cb_new = kvquant.fit_codebooks(grp, bits).astype(cbs.dtype)
    cb = jnp.where(is_first[:, None, None], cb_new, cbs[phys])
    idx = kvquant.assign_codebook(grp, cb)
    wrow = kvquant.pack_rows_jnp(idx.reshape(new.shape), bits)
    return (words.at[phys, off].set(wrow, mode="drop"),
            cbs.at[phys].set(cb, mode="drop"))


def gqa_decode_paged_quant(p, x_t, cache: QuantPagedKVCache, page_table,
                           pos, alive, *, n_heads, n_kv, head_dim,
                           page_size, kv_bits, kv_cb_mode="page",
                           attn_softcap=None, rope_theta=10000.0,
                           query_scale=None):
    """``gqa_decode_paged`` over codebook-quantized KV pages.

    The written token is quantized *before* it is attended, so what the
    kernel reads is exactly what the cache stores — the differential
    oracle is the dense route over the dequantized pools, bit-exact.
    """
    q, k, v = _qkv(p, x_t, n_heads, n_kv, head_dim)
    posb = pos[:, None]
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)

    kw, kcb = _write_slot_quant(cache.k_words, cache.k_cb, page_table, pos,
                                alive, k[:, 0], page_size, kv_bits,
                                kv_cb_mode)
    vw, vcb = _write_slot_quant(cache.v_words, cache.v_cb, page_table, pos,
                                alive, v[:, 0], page_size, kv_bits,
                                kv_cb_mode)
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    o = dispatch.paged_attention_quant(
        q, kw, vw, kcb, vcb, page_table, pos, alive, bits=kv_bits,
        head_dim=head_dim, softcap=attn_softcap, scale=scale,
        mesh=active_mesh())
    return (qmatmul(p, "wo", o),
            QuantPagedKVCache(k_words=kw, v_words=vw, k_cb=kcb, v_cb=vcb))


def mla_decode_paged_quant(p, x_t, cache: QuantPagedMLACache, page_table,
                           pos, alive, *, n_heads, kv_lora, rope_dim,
                           nope_dim, v_dim, page_size, kv_bits,
                           rope_theta=10000.0):
    """Absorbed MLA decode over codebook-quantized latent pages."""
    from repro.models.layers import rms_norm
    b = x_t.shape[0]
    posb = pos[:, None]
    q_nope, q_rope = _mla_q(p, x_t, n_heads, nope_dim, rope_dim, posb,
                            rope_theta)
    dkv = qmatmul(p, "w_dkv", x_t)
    c_kv_t = rms_norm(dkv[..., :kv_lora], p["kv_norm_scale"])
    k_rope_t = apply_rope(dkv[..., None, kv_lora:], posb, rope_theta)[:, :, 0]

    cw, ccb = _write_slot_quant(cache.c_words, cache.c_cb, page_table, pos,
                                alive, c_kv_t[:, 0], page_size, kv_bits,
                                "page")
    rw, rcb = _write_slot_quant(cache.r_words, cache.r_cb, page_table, pos,
                                alive, k_rope_t[:, 0], page_size, kv_bits,
                                "page")

    w_uk = qweight(p, "w_uk").reshape(kv_lora, n_heads, nope_dim)
    q_eff = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_uk)
    ctx = dispatch.mla_paged_attention_quant(
        q_eff, q_rope, cw, rw, ccb, rcb, page_table, pos, alive,
        bits=kv_bits, kv_lora=kv_lora, rope_dim=rope_dim,
        scale=(nope_dim + rope_dim) ** -0.5, mesh=active_mesh())
    w_uv = qweight(p, "w_uv").reshape(kv_lora, n_heads, v_dim)
    o = jnp.einsum("bqhl,lhd->bqhd", ctx, w_uv).reshape(b, 1, n_heads * v_dim)
    return (qmatmul(p, "wo", o),
            QuantPagedMLACache(c_words=cw, r_words=rw, c_cb=ccb, r_cb=rcb))


# ---------------------------------------------------------------------------
# Blockwise prefill (chunked-prompt path, PR 9)
#
# Each engine prefill step runs ONE block of ≤ prefill_chunk new prompt
# tokens through these functions: project + rope the block, write its
# K/V straight into the slot's pages (quantizing token-by-token when
# kv_bits > 0 — the same freeze-on-first-write protocol as decode, so
# pages are a pure function of the written values, independent of the
# block partition), then attend the block's queries over the slot's
# *stored* K/V view via ``dispatch.blockwise_prefill_attention`` — the
# write-then-attend order makes what is attended exactly what the cache
# holds.  The one-shot oracle runs the same per-block functions over
# growing buffers; because view rows carry their positions and invisible
# rows mask to exact zero probability, the engine's fixed-capacity page
# view and the oracle's growing view are bit-identical per block.
# ---------------------------------------------------------------------------


def _write_block_slot(pool: Array, page_table: Array, start, alive: Array,
                      new: Array, page_size: int) -> Array:
    """Blockwise twin of ``_write_slot``: scatter ``c`` consecutive
    entries per slot starting at logical position ``start``.

    pool [P+1, page, ...]; page_table [B, npg]; start scalar or [B];
    new [B, c, ...].  Dead slots write the trash page."""
    b, c = new.shape[0], new.shape[1]
    npg = page_table.shape[1]
    start = jnp.broadcast_to(jnp.asarray(start), (b,))
    t = start[:, None] + jnp.arange(c)[None, :]            # [B, c]
    pg = jnp.clip(t // page_size, 0, npg - 1)
    phys = page_table[jnp.arange(b)[:, None], pg]
    phys = jnp.where(alive[:, None], phys, 0)
    return pool.at[phys, t % page_size].set(new.astype(pool.dtype),
                                            mode="drop")


def _write_block_slot_quant(words: Array, cbs: Array, page_table: Array,
                            start, alive: Array, new: Array, page_size: int,
                            bits: int, cb_mode: str):
    """Blockwise twin of ``_write_slot_quant``: a per-token ``lax.scan``
    over the block so the freeze-on-first-write codebook protocol is the
    decode path's, token for token — a page's codebook is fit by whoever
    writes its offset 0, whether that token arrives in this block, a
    previous one, or (after restore) a replayed one."""
    b, c = new.shape[0], new.shape[1]
    start = jnp.broadcast_to(jnp.asarray(start), (b,))

    def body(carry, xs):
        w, cb = carry
        tok, off = xs
        w, cb = _write_slot_quant(w, cb, page_table, start + off, alive,
                                  tok, page_size, bits, cb_mode)
        return (w, cb), None

    toks = jnp.moveaxis(new, 1, 0)                         # [c, B, ...]
    (w, cb), _ = jax.lax.scan(body, (words, cbs),
                              (toks, jnp.arange(c)))
    return w, cb


def gqa_prefill_block_paged(p, x, cache: PagedKVCache, page_table, start,
                            alive, *, n_heads, n_kv, head_dim, page_size,
                            attn_softcap=None, rope_theta=10000.0,
                            query_scale=None):
    """One prompt block of a paged (global-attention) GQA layer.

    x [B,c,D]; ``cache`` one layer's lane-dense pools [P+1, page,
    KV·hd]; start: the block's first logical position (traced OK).
    Writes the block's K/V into the slot's pages, then attends the block
    queries over the gathered page view — rows beyond ``start + c`` are
    future/garbage and mask out causally (row index == position)."""
    b, c, _ = x.shape
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    t = jnp.asarray(start) + jnp.arange(c)                 # [c]
    q = apply_rope(q, t[None, :], rope_theta)
    k = apply_rope(k, t[None, :], rope_theta)

    ck = _write_block_slot(cache.k, page_table, start, alive,
                           k.reshape(b, c, -1), page_size)
    cv = _write_block_slot(cache.v, page_table, start, alive,
                           v.reshape(b, c, -1), page_size)

    def view(pool):                                        # [B,cap,KV,hd]
        g = _gather_slots(pool, page_table, alive, heads=n_kv)
        return g.reshape(g.shape[:2] + (n_kv, head_dim))

    view_k, view_v = view(ck), view(cv)
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    o = dispatch.blockwise_prefill_attention(
        q, view_k, view_v, t, jnp.arange(view_k.shape[1]),
        softcap=attn_softcap, scale=scale, mesh=active_mesh())
    return (qmatmul(p, "wo", o.reshape(b, c, n_heads * head_dim)),
            PagedKVCache(k=ck, v=cv))


def gqa_prefill_block_paged_quant(p, x, cache: QuantPagedKVCache,
                                  page_table, start, alive, *, n_heads,
                                  n_kv, head_dim, page_size, kv_bits,
                                  kv_cb_mode="page", attn_softcap=None,
                                  rope_theta=10000.0, query_scale=None):
    """``gqa_prefill_block_paged`` over codebook-quantized KV pages: the
    block's tokens quantize one by one at write time, then the block
    attends over the stored packed words — what is read is exactly what
    the cache holds, kv_bits/8 B per cached scalar."""
    b, c, _ = x.shape
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    t = jnp.asarray(start) + jnp.arange(c)
    q = apply_rope(q, t[None, :], rope_theta)
    k = apply_rope(k, t[None, :], rope_theta)

    kw, kcb = _write_block_slot_quant(cache.k_words, cache.k_cb, page_table,
                                      start, alive, k, page_size, kv_bits,
                                      kv_cb_mode)
    vw, vcb = _write_block_slot_quant(cache.v_words, cache.v_cb, page_table,
                                      start, alive, v, page_size, kv_bits,
                                      kv_cb_mode)
    masked_tbl = jnp.where(alive[:, None], page_table, 0)
    kw_view = _gather_slots(kw, page_table, alive)         # [B,cap,KV,Wd]
    vw_view = _gather_slots(vw, page_table, alive)
    kcb_view = kcb[masked_tbl]                             # [B,npg,Gcb,K]
    vcb_view = vcb[masked_tbl]
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    o = dispatch.blockwise_prefill_attention_quant(
        q, kw_view, vw_view, kcb_view, vcb_view, t,
        jnp.arange(kw_view.shape[1]), page_size=page_size, bits=kv_bits,
        head_dim=head_dim, softcap=attn_softcap, scale=scale,
        mesh=active_mesh())
    return (qmatmul(p, "wo", o.reshape(b, c, n_heads * head_dim)),
            QuantPagedKVCache(k_words=kw, v_words=vw, k_cb=kcb, v_cb=vcb))


def _ring_positions(start, cap: int) -> Array:
    """Position held by each ring slot after ``start`` tokens have been
    written: slot j holds p = (start-1) - ((start-1 - j) mod cap), or the
    sentinel when that is negative (slot not yet written)."""
    j = jnp.arange(cap)
    pm1 = jnp.asarray(start) - 1
    pos = pm1 - jnp.mod(pm1 - j, cap)
    return jnp.where(pos >= 0, pos, dispatch.ref.POS_SENTINEL)


def gqa_prefill_block_ring(p, x, cache: KVCache, start, *, n_heads, n_kv,
                           head_dim, window, attn_softcap=None,
                           rope_theta=10000.0, query_scale=None):
    """One prompt block of a sliding-window (ring-buffer) GQA layer.

    The ring (capacity == window) plus the block's fresh K/V form the
    attended view; ring rows carry their true positions (sentinel when
    unwritten — stale rows older than the window mask out by the window
    predicate).  After attending, the last ``min(c, cap)`` tokens land
    in their ring slots.  Used by both the engine (B=1 slot rows) and
    the oracle (batched) — batch-row-decoupled."""
    b, c, _ = x.shape
    cap = cache.k.shape[1]
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    t = jnp.asarray(start) + jnp.arange(c)
    q = apply_rope(q, t[None, :], rope_theta)
    k = apply_rope(k, t[None, :], rope_theta)

    view_k = jnp.concatenate([cache.k, k.astype(cache.k.dtype)], axis=1)
    view_v = jnp.concatenate([cache.v, v.astype(cache.v.dtype)], axis=1)
    k_pos = jnp.concatenate([_ring_positions(start, cap), t])
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    o = dispatch.blockwise_prefill_attention(
        q, view_k, view_v, t, k_pos, window=window, softcap=attn_softcap,
        scale=scale, mesh=active_mesh())

    j = jnp.arange(cap)
    end = jnp.asarray(start) + c - 1
    pos_j = end - jnp.mod(end - j, cap)                    # position slot j
    take = pos_j >= jnp.asarray(start)                     # written this blk
    idx = jnp.clip(pos_j - jnp.asarray(start), 0, c - 1)
    newk = jnp.where(take[None, :, None, None],
                     k.astype(cache.k.dtype)[:, idx], cache.k)
    newv = jnp.where(take[None, :, None, None],
                     v.astype(cache.v.dtype)[:, idx], cache.v)
    return (qmatmul(p, "wo", o.reshape(b, c, n_heads * head_dim)),
            KVCache(k=newk, v=newv))


def gqa_prefill_block(p, x, buf_k, buf_v, start: int, *, n_heads, n_kv,
                      head_dim, window=None, attn_softcap=None,
                      rope_theta=10000.0, query_scale=None):
    """Oracle-side block step of a global GQA layer: append the block's
    K/V to the growing buffers ([B, start, KV, hd] → [B, start+c, ...])
    and attend over the result.  Same per-row math as the engine's page
    view; extra engine rows are all masked, which is a bitwise no-op."""
    b, c, _ = x.shape
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    t = start + jnp.arange(c)
    q = apply_rope(q, t[None, :], rope_theta)
    k = apply_rope(k, t[None, :], rope_theta)
    bk = jnp.concatenate([buf_k, k.astype(buf_k.dtype)], axis=1)
    bv = jnp.concatenate([buf_v, v.astype(buf_v.dtype)], axis=1)
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    o = dispatch.blockwise_prefill_attention(
        q, bk, bv, t, jnp.arange(bk.shape[1]), window=window,
        softcap=attn_softcap, scale=scale, mesh=active_mesh())
    return qmatmul(p, "wo", o.reshape(b, c, n_heads * head_dim)), bk, bv


def mla_prefill_block_paged(p, x, cache: PagedMLACache, page_table, start,
                            alive, *, n_heads, kv_lora, rope_dim, nope_dim,
                            v_dim, page_size, rope_theta=10000.0):
    """One prompt block of an MLA layer over the paged latent cache.

    Prefill stays in the *expanded* space: the block's latent rows are
    written to the slot's pages, the page view is re-expanded through
    W_UK/W_UV (row-wise matmuls — identical per row regardless of view
    length), and the block runs the dense blockwise-attention op with
    per-head keys of width nope+rope and values of width v_dim."""
    from repro.models.layers import rms_norm
    b, c, _ = x.shape
    t = jnp.asarray(start) + jnp.arange(c)
    q_nope, q_rope = _mla_q(p, x, n_heads, nope_dim, rope_dim, t[None, :],
                            rope_theta)
    dkv = qmatmul(p, "w_dkv", x)
    c_kv = rms_norm(dkv[..., :kv_lora], p["kv_norm_scale"])
    k_rope = apply_rope(dkv[..., None, kv_lora:], t[None, :],
                        rope_theta)[:, :, 0]

    ckv = _write_block_slot(cache.c_kv, page_table, start, alive, c_kv,
                            page_size)
    krope = _write_block_slot(cache.k_rope, page_table, start, alive,
                              k_rope, page_size)
    c_view = _gather_slots(ckv, page_table, alive)         # [B,cap,lora]
    r_view = _gather_slots(krope, page_table, alive)       # [B,cap,rope]
    o = _mla_block_attend(p, q_nope, q_rope, c_view, r_view, t,
                          n_heads=n_heads, nope_dim=nope_dim,
                          rope_dim=rope_dim, v_dim=v_dim)
    return (qmatmul(p, "wo", o.reshape(b, c, n_heads * v_dim)),
            PagedMLACache(c_kv=ckv, k_rope=krope))


def _mla_block_attend(p, q_nope, q_rope, c_view, r_view, t, *, n_heads,
                      nope_dim, rope_dim, v_dim):
    """Expand a latent view and attend one block's queries over it."""
    b, s = c_view.shape[0], c_view.shape[1]
    k_nope = qmatmul(p, "w_uk", c_view).reshape(b, s, n_heads, nope_dim)
    v = qmatmul(p, "w_uv", c_view).reshape(b, s, n_heads, v_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(r_view[:, :, None, :],
                                  (b, s, n_heads, rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return dispatch.blockwise_prefill_attention(
        q, k, v, t, jnp.arange(s), scale=(nope_dim + rope_dim) ** -0.5,
        mesh=active_mesh())


def mla_prefill_block_paged_quant(p, x, cache: QuantPagedMLACache,
                                  page_table, start, alive, *, n_heads,
                                  kv_lora, rope_dim, nope_dim, v_dim,
                                  page_size, kv_bits, rope_theta=10000.0):
    """MLA block prefill over codebook-quantized latent pages: per-token
    quantizing writes (decode's freeze-on-first-write protocol), then the
    latent view is dequantized (jnp — the expansion matmuls need dense
    latents anyway, so there is no fused-quant MLA prefill kernel
    variant) and re-expanded exactly as the dense path."""
    from repro.kernels.ref import dequant_view_ref
    from repro.models.layers import rms_norm
    b, c, _ = x.shape
    t = jnp.asarray(start) + jnp.arange(c)
    q_nope, q_rope = _mla_q(p, x, n_heads, nope_dim, rope_dim, t[None, :],
                            rope_theta)
    dkv = qmatmul(p, "w_dkv", x)
    c_kv = rms_norm(dkv[..., :kv_lora], p["kv_norm_scale"])
    k_rope = apply_rope(dkv[..., None, kv_lora:], t[None, :],
                        rope_theta)[:, :, 0]

    cw, ccb = _write_block_slot_quant(cache.c_words, cache.c_cb, page_table,
                                      start, alive, c_kv, page_size,
                                      kv_bits, "page")
    rw, rcb = _write_block_slot_quant(cache.r_words, cache.r_cb, page_table,
                                      start, alive, k_rope, page_size,
                                      kv_bits, "page")
    masked_tbl = jnp.where(alive[:, None], page_table, 0)
    c_view = dequant_view_ref(_gather_slots(cw, page_table, alive),
                              ccb[masked_tbl], kv_lora, kv_bits, page_size)
    r_view = dequant_view_ref(_gather_slots(rw, page_table, alive),
                              rcb[masked_tbl], rope_dim, kv_bits, page_size)
    o = _mla_block_attend(p, q_nope, q_rope, c_view.astype(ccb.dtype),
                          r_view.astype(rcb.dtype), t, n_heads=n_heads,
                          nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim)
    return (qmatmul(p, "wo", o.reshape(b, c, n_heads * v_dim)),
            QuantPagedMLACache(c_words=cw, r_words=rw, c_cb=ccb, r_cb=rcb))


def mla_prefill_block(p, x, buf_c, buf_r, start: int, *, n_heads, kv_lora,
                      rope_dim, nope_dim, v_dim, rope_theta=10000.0):
    """Oracle-side MLA block step: append the block's latent rows to the
    growing buffers and attend over the re-expansion of the result."""
    from repro.models.layers import rms_norm
    b, c, _ = x.shape
    t = start + jnp.arange(c)
    q_nope, q_rope = _mla_q(p, x, n_heads, nope_dim, rope_dim, t[None, :],
                            rope_theta)
    dkv = qmatmul(p, "w_dkv", x)
    c_kv = rms_norm(dkv[..., :kv_lora], p["kv_norm_scale"])
    k_rope = apply_rope(dkv[..., None, kv_lora:], t[None, :],
                        rope_theta)[:, :, 0]
    bc = jnp.concatenate([buf_c, c_kv.astype(buf_c.dtype)], axis=1)
    br = jnp.concatenate([buf_r, k_rope.astype(buf_r.dtype)], axis=1)
    o = _mla_block_attend(p, q_nope, q_rope, bc, br, t, n_heads=n_heads,
                          nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim)
    return qmatmul(p, "wo", o.reshape(b, c, n_heads * v_dim)), bc, br


def gqa_decode_ring_slots(p, x_t, cache: KVCache, pos, alive, *, n_heads,
                          n_kv, head_dim, window, attn_softcap=None,
                          rope_theta=10000.0, query_scale=None):
    """Sliding-window decode with a per-slot position vector.

    The ring buffer is per-slot constant size (capacity == cache cap);
    the engine never pages it — it just resets on admission.
    """
    b = x_t.shape[0]
    q, k, v = _qkv(p, x_t, n_heads, n_kv, head_dim)
    posb = pos[:, None]
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)

    cap = cache.k.shape[1]
    slot = pos % cap
    rows = jnp.arange(b)
    ck = cache.k.at[rows, slot].set(k[:, 0].astype(cache.k.dtype))
    cv = cache.v.at[rows, slot].set(v[:, 0].astype(cache.v.dtype))

    idx = jnp.arange(cap)[None, :]
    # ring slot i holds position p_i = pos - ((pos - i) mod cap)
    slot_pos = posb - jnp.mod(posb - idx, cap)
    valid = ((slot_pos >= 0) & (slot_pos > posb - (window or cap))
             & alive[:, None])
    scale = query_scale if query_scale is not None else head_dim ** -0.5
    o = _slot_attention(q, ck, cv, valid, n_heads=n_heads, n_kv=n_kv,
                        head_dim=head_dim, attn_softcap=attn_softcap,
                        scale=scale)
    return qmatmul(p, "wo", o), KVCache(k=ck, v=cv)


def mla_decode_paged(p, x_t, cache: PagedMLACache, page_table, pos, alive, *,
                     n_heads, kv_lora, rope_dim, nope_dim, v_dim, page_size,
                     rope_theta=10000.0):
    """Absorbed MLA decode over the paged latent cache (per-slot pos)."""
    from repro.models.layers import rms_norm
    b = x_t.shape[0]
    posb = pos[:, None]
    q_nope, q_rope = _mla_q(p, x_t, n_heads, nope_dim, rope_dim, posb,
                            rope_theta)
    dkv = qmatmul(p, "w_dkv", x_t)
    c_kv_t = rms_norm(dkv[..., :kv_lora], p["kv_norm_scale"])
    k_rope_t = apply_rope(dkv[..., None, kv_lora:], posb, rope_theta)[:, :, 0]

    ckv = _write_slot(cache.c_kv, page_table, pos, alive, c_kv_t[:, 0],
                      page_size)
    krope = _write_slot(cache.k_rope, page_table, pos, alive, k_rope_t[:, 0],
                        page_size)

    w_uk = qweight(p, "w_uk").reshape(kv_lora, n_heads, nope_dim)
    q_eff = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_uk)
    # fused absorbed-MLA paged decode (the ref route is the verbatim
    # former gather + latent-softmax einsum chain, bit-identical)
    ctx = dispatch.mla_paged_attention(
        q_eff, q_rope, ckv, krope, page_table, pos, alive,
        scale=(nope_dim + rope_dim) ** -0.5, mesh=active_mesh())
    w_uv = qweight(p, "w_uv").reshape(kv_lora, n_heads, v_dim)
    o = jnp.einsum("bqhl,lhd->bqhd", ctx, w_uv).reshape(b, 1, n_heads * v_dim)
    return qmatmul(p, "wo", o), PagedMLACache(c_kv=ckv, k_rope=krope)
