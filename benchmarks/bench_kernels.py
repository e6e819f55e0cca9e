"""Kernel microbenchmarks: Pallas (interpret mode — correctness-grade
timing only on CPU) vs the jnp reference, plus serving-path byte
accounting (the roofline story of codebook_matmul).

Byte accounting uses ``compression.bits_per_index(k)`` — the eq.-14 index
width — so the roofline row is correct for any K, and the packed-route
rows report the *actual* HBM bytes of the uint32 word operand
(``pidx.nbytes``), which must equal bits/8 per weight (+ codebook).
Gather rows report the *gathered traffic* per weight (one packed word
row per token on the ``pack_rows`` serving layout — bits/8; the pre-PR-4
accounting quoted resident word bytes while the jnp column-layout route
actually read 4 B/word per gathered column).  Every such row is enforced
by tests/test_bench_accounting.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import compression, kvquant
from repro.kernels import dispatch, ops, ref


def _accounting(kd: int, n: int, k: int) -> str:
    bits = compression.bits_per_index(k)
    lanes = 32 // bits
    bytes_bf16 = kd * n * 2
    # Actual pack_indices_2d word-layout bytes (not the entropy formula):
    # lane counts that don't divide 32 waste the word's top bits.
    bytes_packed = -(-kd // lanes) * n * 4 + k * 4
    return (f"weight_bytes bf16={bytes_bf16} packed={bytes_packed} "
            f"({bits}-bit; x{bytes_bf16 / bytes_packed:.1f} HBM reduction "
            f"at decode)")


def run():
    rows = []
    key = jax.random.PRNGKey(0)

    # -- roofline accounting row (prefill-ish shape, K=16) -------------------
    m, kd, n, k = 256, 2048, 512, 16
    x = jax.random.normal(key, (m, kd), jnp.float32)
    idx = jax.random.randint(key, (kd, n), 0, k).astype(jnp.uint8)
    cb = jax.random.normal(key, (k,))

    us_ref = time_call(jax.jit(ref.codebook_matmul_ref), x, idx, cb,
                       warmup=2, iters=5)
    rows.append(("codebook_matmul_ref_jit", us_ref, _accounting(kd, n, k)))

    us_pal = time_call(lambda *a: ops.codebook_matmul(*a, bm=128, bn=128,
                                                      bk=512), x, idx, cb,
                       warmup=1, iters=2)
    rows.append(("codebook_matmul_pallas_interpret", us_pal,
                 "interpret-mode (correctness only; TPU target)"))

    # -- packed vs uint8 vs ref across the serving K range -------------------
    # kd2 is a multiple of 32 so every lane count packs without a ragged
    # tail and pidx.nbytes is exactly bits/8 per weight.
    m2, kd2, n2 = 64, 1024, 256
    x2 = jax.random.normal(key, (m2, kd2), jnp.float32)
    rng = np.random.RandomState(0)
    for k in (2, 4, 16, 256):
        bits = compression.bits_per_index(k)
        idx_np = rng.randint(0, k, size=(kd2, n2))
        idx2 = jnp.asarray(idx_np.astype(np.uint8))
        pidx = jnp.asarray(compression.pack_indices_2d(idx_np, k))
        cb2 = jax.random.normal(jax.random.fold_in(key, k), (k,))
        bm, bn, bk = dispatch.packed_block_sizes(m2, kd2, n2, bits)

        us = time_call(jax.jit(ref.codebook_matmul_ref), x2, idx2, cb2,
                       warmup=2, iters=5)
        rows.append((f"codebook_matmul_ref_K{k}", us,
                     f"dense-gather oracle ({bits}-bit indices)"))

        us = time_call(lambda *a: ops.codebook_matmul(*a, bm=bm, bn=bn,
                                                      bk=bk),
                       x2, idx2, cb2, warmup=1, iters=2)
        rows.append((f"codebook_matmul_uint8_interp_K{k}", us,
                     "idx_bytes/weight=1.0 (uint8 HBM layout)"))

        us = time_call(lambda *a: ops.packed_codebook_matmul(
            *a, bm=bm, bn=bn, bk=bk), x2, pidx, cb2, warmup=1, iters=2)
        bpw = pidx.size * 4 / (kd2 * n2)
        expect = bits / 8
        flag = "" if abs(bpw - expect) < 1e-9 else " MISMATCH"
        rows.append((
            f"codebook_matmul_packed_interp_K{k}", us,
            f"idx_bytes/weight={bpw:.4f} (== bits_per_index/8 = "
            f"{expect:.4f}{flag}; +{k * 4} B codebook; "
            f"blocks bm={bm} bn={bn} bk={bk})"))

    # -- attention-projection packed route (full-model qleaf serving) --------
    # q/k/v/o-ish shape: d_model → n_heads·head_dim at a prefill batch.
    m3, kd3, n3 = 128, 512, 512
    x3 = jax.random.normal(key, (m3, kd3), jnp.float32)
    for k in (4, 16):
        bits = compression.bits_per_index(k)
        idx_np = rng.randint(0, k, size=(kd3, n3))
        pidx = jnp.asarray(compression.pack_indices_2d(idx_np, k))
        cb3 = jax.random.normal(jax.random.fold_in(key, 100 + k), (k,))
        bm, bn, bk = dispatch.packed_block_sizes(m3, kd3, n3, bits)
        us = time_call(lambda *a: ops.packed_codebook_matmul(
            *a, bm=bm, bn=bn, bk=bk), x3, pidx, cb3, warmup=1, iters=2)
        bpw = pidx.size * 4 / (kd3 * n3)
        expect = bits / 8
        flag = "" if abs(bpw - expect) < 1e-9 else " MISMATCH"
        rows.append((
            f"codebook_matmul_packed_attn_K{k}", us,
            f"idx_bytes/weight={bpw:.4f} (== bits_per_index/8 = "
            f"{expect:.4f}{flag}; +{k * 4} B codebook; qkv-proj shape "
            f"{m3}x{kd3}x{n3}; blocks bm={bm} bn={bn} bk={bk})"))

    # -- embedding dequant-on-gather (packed table, no dense [V, D]) ---------
    # The serving layout is row-packed (pack_rows): a token's lookup reads
    # its contiguous word row — ⌈D/lanes⌉·4 B per token, i.e. exactly
    # bits/8 *index bytes per gathered weight* (d4 is a multiple of 32 so
    # every lane count divides).  The pre-row-pack jnp fallback gathered
    # one full uint32 word per embedding column: 4 B/weight.
    v4, d4 = 4096, 256
    toks = jnp.asarray(rng.randint(0, v4, size=(8, 32)), jnp.int32)
    toks_m = toks[:2]              # 64 tokens: interpret-mode grid is 1/row
    for k in (2, 16, 256):
        bits = compression.bits_per_index(k)
        idx_np = rng.randint(0, k, size=(v4, d4))
        pidx_r = jnp.asarray(compression.pack_rows(idx_np, k))
        cb4 = jax.random.normal(jax.random.fold_in(key, 200 + k), (k,))
        layout = compression.PackedLayout.make(v4, d4, k, order="row")
        # Gathered HBM index bytes per gathered weight (the serve-path
        # traffic — NOT the resident word-array bytes per table weight),
        # measured from the actual packed operand's row width so a
        # pack_rows layout regression trips the MISMATCH flag.
        bpw = pidx_r.shape[1] * 4 / d4
        expect = bits / 8
        flag = "" if abs(bpw - expect) < 1e-9 else " MISMATCH"
        note = (f"idx_bytes/weight={bpw:.4f} (== bits_per_index/8 = "
                f"{expect:.4f}{flag}; +{k * 4} B codebook; "
                f"table {v4}x{d4})")

        gather = jax.jit(lambda t, w, c: dispatch.quantized_gather(
            t, w, c, layout=layout, backend="ref"))
        us = time_call(gather, toks, pidx_r, cb4, warmup=2, iters=5)
        dense_tbl = jnp.asarray(cb4)[jnp.asarray(idx_np)]
        us_d = time_call(jax.jit(lambda t, w: w[t]), toks, dense_tbl,
                         warmup=2, iters=5)
        rows.append((
            f"quantized_gather_embed_K{k}", us,
            f"{note[:-1]}; 256 tokens, jnp row-gather reference; dense "
            f"f32 gather {us_d:.1f}us / {v4 * d4 * 4} B resident)"))

        us = time_call(lambda t, w, c: ops.quantized_gather(
            t, w, c, d4), toks_m.reshape(-1), pidx_r, cb4,
            warmup=1, iters=2)
        rows.append((
            f"quantized_gather_mosaic_K{k}", us,
            f"{note[:-1]}; 64 tokens, scalar-prefetch row DMA, "
            f"interpret-mode)"))

    # -- fused transposed LM head (tied embedding; packed words stay HBM) ----
    # y[M, V] = x[M, D]·W.T over the row-packed [V, ⌈D/lanes⌉] serving
    # operand — the route that replaces dequant-then-dot for the tied head.
    m5, d5, v5 = 8, 256, 1024
    x5 = jax.random.normal(key, (m5, d5), jnp.float32)
    for k in (2, 16, 256):
        bits = compression.bits_per_index(k)
        idx_np = rng.randint(0, k, size=(v5, d5))
        pidx_r = jnp.asarray(compression.pack_rows(idx_np, k))
        cb5 = jax.random.normal(jax.random.fold_in(key, 300 + k), (k,))
        bm, bn, bk = dispatch.packed_block_sizes_t(m5, d5, v5, bits, "row")
        us = time_call(lambda *a: ops.packed_codebook_matmul_t(
            *a, v5, order="row", bm=bm, bn=bn, bk=bk), x5, pidx_r, cb5,
            warmup=1, iters=2)
        bpw = pidx_r.size * 4 / (v5 * d5)
        expect = bits / 8
        flag = "" if abs(bpw - expect) < 1e-9 else " MISMATCH"
        rows.append((
            f"codebook_matmul_packed_t_K{k}", us,
            f"idx_bytes/weight={bpw:.4f} (== bits_per_index/8 = "
            f"{expect:.4f}{flag}; +{k * 4} B codebook; LM-head shape "
            f"{m5}x{d5}x{v5}; blocks bm={bm} bn={bn} bk={bk})"))

    # -- paged-attention decode (dense + codebook-quantized KV pages) --------
    # The KV B/token note is measured from the materialized pool arrays
    # (word bytes per cached token per tensor — codebooks amortize per
    # page and are quoted separately), and must equal kv_bits/8 ·
    # head_dim · n_kv — the eq.-14 activation accounting
    # tests/test_bench_accounting.py enforces on every such row.  Dense
    # rows report the same identity at kv_bits=32 (4 B/scalar).  head_dim
    # is a multiple of every lane count so rows pack with no ragged tail;
    # token tiles come from dispatch._PAGED_BLOCK_TABLE (the committed
    # winners this bench measures).
    def _kv_note(actual_bpt, bits_eff, hd, nkv, page, tile, cb_b):
        expect = bits_eff / 8 * hd * nkv
        flag = "" if abs(actual_bpt - expect) < 1e-9 else " MISMATCH"
        return (f"kv_bytes/token={actual_bpt:g} (== kv_bits/8*head_dim*"
                f"n_kv = {expect:g}{flag}; kv_bits={bits_eff} "
                f"head_dim={hd} n_kv={nkv}; +{cb_b} B codebook/page; "
                f"page={page} tile={tile})")

    bq, hq, kvh, hd6, page6, npg6 = 4, 4, 2, 32, 8, 3
    pp1 = bq * npg6 + 1                         # pool pages incl. trash
    kp = jax.random.normal(jax.random.fold_in(key, 400),
                           (pp1, page6, kvh, hd6), jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(key, 401), kp.shape)
    q6 = jax.random.normal(jax.random.fold_in(key, 402),
                           (bq, 1, hq, hd6), jnp.float32)
    tbl6 = jnp.asarray(rng.permutation(np.arange(1, pp1)
                                       ).reshape(bq, npg6), jnp.int32)
    pos6 = jnp.asarray([20, 13, 7, 2], jnp.int32)
    alive6 = jnp.asarray([True, True, True, False])
    scale6 = hd6 ** -0.5

    tile = dispatch.paged_token_tile("gqa", kvh * hd6, page6, 0)
    note = _kv_note(kvh * hd6 * 4, 32, hd6, kvh, page6, tile, 0)
    us = time_call(jax.jit(lambda *a: ref.paged_attention_ref(
        *a, softcap=None, scale=scale6)), q6, kp, vp, tbl6, pos6, alive6,
        warmup=2, iters=5)
    rows.append(("paged_attention_gqa_ref_dense", us,
                 f"{note}; jnp gather+softmax oracle"))
    # the kernel reads the engine's stored form: one layer of lane-dense
    # pools [G, P+1, page, KV·hd]
    us = time_call(lambda q, k, v, *a: ops.paged_attention(
        q, k, v, jnp.zeros((), jnp.int32), *a, softcap=None, scale=scale6,
        token_tile=tile, interpret=True),
        q6, kp.reshape(1, pp1, page6, -1), vp.reshape(1, pp1, page6, -1),
        tbl6, pos6, alive6, warmup=1, iters=2)
    rows.append(("paged_attention_gqa_interp_dense", us,
                 f"{note}; scalar-prefetch fused kernel, interpret-mode"))

    def _quantize_pool(pool, bits):
        grp = pool.reshape(pool.shape[0], 1, -1)
        cb = kvquant.fit_codebooks(grp, bits)
        idx = kvquant.assign_codebook(grp, cb).reshape(pool.shape)
        return kvquant.pack_rows_jnp(idx, bits), cb

    for bits in (2, 4, 8):
        kw, kcb = _quantize_pool(kp, bits)
        vw, vcb = _quantize_pool(vp, bits)
        tile = dispatch.paged_token_tile("gqa", kvh * hd6, page6, bits)
        bpt = kw[0].nbytes / page6               # words/token/tensor
        cb_b = kcb[0].nbytes
        note = _kv_note(bpt, bits, hd6, kvh, page6, tile, cb_b)
        if bits == 4:
            us = time_call(jax.jit(lambda *a: ref.paged_attention_quant_ref(
                *a, bits=4, head_dim=hd6, softcap=None, scale=scale6)),
                q6, kw, vw, kcb, vcb, tbl6, pos6, alive6,
                warmup=2, iters=5)
            rows.append(("paged_attention_gqa_ref_kvq4", us,
                         f"{note}; dequant-pages oracle"))
        us = time_call(lambda *a: ops.paged_attention_quant(
            *a, bits=bits, head_dim=hd6, softcap=None, scale=scale6,
            token_tile=tile, interpret=True),
            q6, kw, vw, kcb, vcb, tbl6, pos6, alive6, warmup=1, iters=2)
        rows.append((f"paged_attention_gqa_interp_kvq{bits}", us,
                     f"{note}; in-kernel shift+mask dequant, "
                     f"interpret-mode"))

    # absorbed-MLA latent pages: one "head" of kv_lora + rope_dim feats
    lat7, rd7 = 32, 16
    cp = jax.random.normal(jax.random.fold_in(key, 410),
                           (pp1, page6, lat7), jnp.float32)
    rp = jax.random.normal(jax.random.fold_in(key, 411),
                           (pp1, page6, rd7), jnp.float32)
    qe = jax.random.normal(jax.random.fold_in(key, 412),
                           (bq, 1, hq, lat7), jnp.float32)
    qr = jax.random.normal(jax.random.fold_in(key, 413),
                           (bq, 1, hq, rd7), jnp.float32)
    scale7 = (lat7 + rd7) ** -0.5
    tile = dispatch.paged_token_tile("mla", lat7 + rd7, page6, 0)
    note = _kv_note((lat7 + rd7) * 4, 32, lat7 + rd7, 1, page6, tile, 0)
    us = time_call(lambda *a: ops.mla_paged_attention(
        *a, scale=scale7, token_tile=tile, interpret=True),
        qe, qr, cp, rp, tbl6, pos6, alive6, warmup=1, iters=2)
    rows.append(("paged_attention_mla_interp_dense", us,
                 f"{note}; latent pages, interpret-mode"))

    cw, ccb = _quantize_pool(cp, 4)
    rw, rcb = _quantize_pool(rp, 4)
    tile = dispatch.paged_token_tile("mla", lat7 + rd7, page6, 4)
    bpt = (cw[0].nbytes + rw[0].nbytes) / page6
    note = _kv_note(bpt, 4, lat7 + rd7, 1, page6, tile,
                    ccb[0].nbytes + rcb[0].nbytes)
    us = time_call(lambda *a: ops.mla_paged_attention_quant(
        *a, bits=4, kv_lora=lat7, rope_dim=rd7, scale=scale7,
        token_tile=tile, interpret=True),
        qe, qr, cw, rw, ccb, rcb, tbl6, pos6, alive6, warmup=1, iters=2)
    rows.append(("paged_attention_mla_interp_kvq4", us,
                 f"{note}; quantized latent pages, interpret-mode"))

    # standalone page gather (the non-fused slot view)
    us = time_call(jax.jit(ref.gather_pages_ref), kp, tbl6, alive6,
                   warmup=2, iters=5)
    rows.append(("page_gather_ref_dense", us,
                 f"alive-masked table gather; pool {pp1}x{page6}x"
                 f"{kvh}x{hd6}"))
    us = time_call(lambda *a: ops.page_gather(*a, interpret=True),
                   kp, tbl6, alive6, warmup=1, iters=2)
    rows.append(("page_gather_interp_dense", us,
                 "scalar-prefetch page DMA, interpret-mode"))

    # -- kmeans assign -------------------------------------------------------
    p = 1 << 20
    w = jax.random.normal(key, (p,))
    cbk = jnp.sort(jax.random.normal(key, (16,)))
    us = time_call(jax.jit(lambda w, c: ref.kmeans_assign_ref(w, c)[1]),
                   w, cbk, warmup=2, iters=5)
    rows.append(("kmeans_assign_ref_jit_1M", us, f"{p/(us*1e-6)/1e6:.0f}Mw/s"))
    us = time_call(lambda w, c: ops.kmeans_assign(w, c)[1], w, cbk,
                   warmup=1, iters=2)
    rows.append(("kmeans_assign_pallas_interpret_1M", us,
                 "interpret-mode (correctness only; TPU target)"))
    return rows


if __name__ == "__main__":
    for row in run():
        print(",".join(map(str, row)))
