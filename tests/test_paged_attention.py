"""Differential acceptance suite for the paged-attention kernel family
and the codebook-quantized KV cache.

Layered oracles, each proved against the one below it:

1. ``core.kvquant`` primitives — jit-side ``pack_rows_jnp`` is
   bit-identical to the host packer and round-trips; first-write fits
   with K ≥ N values are lossless;
2. quant refs == dense refs **bit-exactly** when the dense ref runs on
   the dequantized pools ({gqa, mla}, page- and head-grouped codebooks)
   — quantization and attention commute by construction;
3. Pallas kernels (interpret mode; test_tpu_compile.py compiles the
   Mosaic lowering) ≈ the jnp refs for dense and quantized pages;
4. one decode step over quantized pages stays within the codebook
   distortion bound of the dense step on the original values (tighter
   as kv_bits grows);
5. the engine: ``kv_bits=0`` streams are **bit-exact** to the one-shot
   oracle across {gqa-mixed, mla} × weight-packing K ∈ {2, 16} (the
   dispatch rerouting changed no numerics); quantized engines are
   deterministic across reruns *and* slot counts, with every request
   typed FINISHED.

Plus the dead-slot regression: ``_gather_slots``/``page_gather`` mask
the page table with ``alive`` so a freed slot's stale table entries
never gather live pages (pre-PR they materialized whatever the dead
table pointed at).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import mixed_cfg, pack_model
from repro.core import compression, kvquant
from repro.engine import Engine, Request, greedy_generate, truncate_at_eos
from repro.kernels import dispatch, ops, ref
from repro.models import attention as attn

# ---------------------------------------------------------------------------
# shared kernel-level fixture: 3 slots (one dead), 6 usable pages
# ---------------------------------------------------------------------------

B, H, KV, HD, PAGE, NPG = 3, 4, 2, 8, 4, 2
NP_POOL = B * NPG                       # physical pages 1..6; 0 = trash
LAT, RD = 16, 8                         # MLA latent + rope dims
TBL = np.array([[1, 2], [3, 0], [4, 5]], np.int32)
POS = np.array([5, 2, 3], np.int32)
ALIVE = np.array([True, True, False])
SCALE = HD ** -0.5


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


@functools.lru_cache(maxsize=None)
def _gqa_case():
    kp = _rand((NP_POOL + 1, PAGE, KV, HD), 0)
    vp = _rand((NP_POOL + 1, PAGE, KV, HD), 1)
    q = _rand((B, 1, H, HD), 2)
    return q, kp, vp, jnp.asarray(TBL), jnp.asarray(POS), jnp.asarray(ALIVE)


@functools.lru_cache(maxsize=None)
def _mla_case():
    cp = _rand((NP_POOL + 1, PAGE, LAT), 3)
    rp = _rand((NP_POOL + 1, PAGE, RD), 4)
    qe = _rand((B, 1, H, LAT), 5)
    qr = _rand((B, 1, H, RD), 6)
    return qe, qr, cp, rp, jnp.asarray(TBL), jnp.asarray(POS), \
        jnp.asarray(ALIVE)


def _quant_pool(pool, bits, mode="page"):
    """(words, cbs, dequantized_pool) for a dense page pool."""
    if pool.ndim == 4 and mode == "head":
        pp1, page, kvh, hd = pool.shape
        grp = jnp.transpose(pool, (0, 2, 1, 3)).reshape(pp1, kvh,
                                                        page * hd)
        cbs = kvquant.fit_codebooks(grp, bits)
        idx = kvquant.assign_codebook(grp, cbs)
        deq = jnp.transpose(
            kvquant.dequant_codebook(idx, cbs).reshape(pp1, kvh, page, hd),
            (0, 2, 1, 3))
        idx = jnp.transpose(idx.reshape(pp1, kvh, page, hd), (0, 2, 1, 3))
    else:
        grp = pool.reshape(pool.shape[0], 1, -1)
        cbs = kvquant.fit_codebooks(grp, bits)
        idx = kvquant.assign_codebook(grp, cbs)
        deq = kvquant.dequant_codebook(idx, cbs).reshape(pool.shape)
        idx = idx.reshape(pool.shape)
    return kvquant.pack_rows_jnp(idx, bits), cbs, deq


# ---------------------------------------------------------------------------
# 1. kvquant primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", kvquant.KV_BITS_CHOICES)
def test_pack_rows_jnp_matches_host_packer_and_roundtrips(bits):
    k = kvquant.kv_entries(bits)
    idx = np.random.RandomState(bits).randint(0, k, size=(7, 13))
    jit_words = np.asarray(kvquant.pack_rows_jnp(jnp.asarray(idx), bits))
    host_words = compression.pack_rows(idx, k)
    np.testing.assert_array_equal(jit_words, host_words)
    back = compression.unpack_rows(host_words, 13, k)
    np.testing.assert_array_equal(back, idx)


@pytest.mark.parametrize("bits", kvquant.KV_BITS_CHOICES)
def test_first_write_fit_is_lossless_when_entries_cover_values(bits):
    """A page's freeze-on-first-write codebook is fit from ≤ K distinct
    values at decode-time first touch — each value becomes its own
    centroid, so the stored dequant is exact."""
    k = kvquant.kv_entries(bits)
    n = min(k, 9)
    vals = jnp.asarray(np.random.RandomState(1).randn(2, 1, n),
                       jnp.float32)
    cbs = kvquant.fit_codebooks(vals, bits)
    idx = kvquant.assign_codebook(vals, cbs)
    np.testing.assert_array_equal(
        np.asarray(kvquant.dequant_codebook(idx, cbs)), np.asarray(vals))


def test_kv_byte_accounting_identities():
    assert kvquant.kv_bytes_per_token(4, 128, 8) == 0.5 * 128 * 8
    dense = kvquant.dense_page_bytes(16, 128)
    for bits in kvquant.KV_BITS_CHOICES:
        q = kvquant.quant_page_bytes(16, 128, bits, 1)
        assert q < dense
    with pytest.raises(ValueError):
        kvquant.check_kv_bits(3)


# ---------------------------------------------------------------------------
# 2. quant refs == dense refs on the dequantized pools (bit-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", kvquant.KV_BITS_CHOICES)
@pytest.mark.parametrize("mode", ["page", "head"])
def test_gqa_quant_ref_is_dense_ref_on_dequantized_pool(bits, mode):
    q, kp, vp, tbl, pos, alive = _gqa_case()
    kw, kcb, kdeq = _quant_pool(kp, bits, mode)
    vw, vcb, vdeq = _quant_pool(vp, bits, mode)
    out_q = ref.paged_attention_quant_ref(
        q, kw, vw, kcb, vcb, tbl, pos, alive, bits=bits, head_dim=HD,
        softcap=None, scale=SCALE)
    out_d = ref.paged_attention_ref(q, kdeq, vdeq, tbl, pos, alive,
                                    softcap=None, scale=SCALE)
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_d))


@pytest.mark.parametrize("bits", kvquant.KV_BITS_CHOICES)
def test_mla_quant_ref_is_dense_ref_on_dequantized_pool(bits):
    qe, qr, cp, rp, tbl, pos, alive = _mla_case()
    cw, ccb, cdeq = _quant_pool(cp, bits)
    rw, rcb, rdeq = _quant_pool(rp, bits)
    out_q = ref.mla_paged_attention_quant_ref(
        qe, qr, cw, rw, ccb, rcb, tbl, pos, alive, bits=bits,
        kv_lora=LAT, rope_dim=RD, scale=(LAT + RD) ** -0.5)
    out_d = ref.mla_paged_attention_ref(qe, qr, cdeq, rdeq, tbl, pos,
                                        alive, scale=(LAT + RD) ** -0.5)
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_d))


# ---------------------------------------------------------------------------
# 3. Pallas kernels (interpret mode) vs refs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile", [1, 2, 4])
def test_gqa_pallas_interpret_matches_ref(tile):
    q, kp, vp, tbl, pos, alive = _gqa_case()
    want = ref.paged_attention_ref(q, kp, vp, tbl, pos, alive,
                                   softcap=None, scale=SCALE)
    got = ops.paged_attention(q, kp, vp, tbl, pos, alive, softcap=None,
                              scale=SCALE, token_tile=tile, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[ALIVE],
                               np.asarray(want)[ALIVE],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", kvquant.KV_BITS_CHOICES)
def test_gqa_quant_pallas_interpret_matches_quant_ref(bits):
    q, kp, vp, tbl, pos, alive = _gqa_case()
    kw, kcb, _ = _quant_pool(kp, bits)
    vw, vcb, _ = _quant_pool(vp, bits)
    want = ref.paged_attention_quant_ref(
        q, kw, vw, kcb, vcb, tbl, pos, alive, bits=bits, head_dim=HD,
        softcap=None, scale=SCALE)
    got = ops.paged_attention_quant(
        q, kw, vw, kcb, vcb, tbl, pos, alive, bits=bits, head_dim=HD,
        softcap=None, scale=SCALE, token_tile=2, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[ALIVE],
                               np.asarray(want)[ALIVE],
                               rtol=2e-5, atol=2e-5)


def test_mla_pallas_interpret_matches_ref_dense_and_quant():
    qe, qr, cp, rp, tbl, pos, alive = _mla_case()
    scale = (LAT + RD) ** -0.5
    want = ref.mla_paged_attention_ref(qe, qr, cp, rp, tbl, pos, alive,
                                       scale=scale)
    got = ops.mla_paged_attention(qe, qr, cp, rp, tbl, pos, alive,
                                  scale=scale, token_tile=2,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got)[ALIVE],
                               np.asarray(want)[ALIVE],
                               rtol=2e-5, atol=2e-5)
    cw, ccb, _ = _quant_pool(cp, 4)
    rw, rcb, _ = _quant_pool(rp, 4)
    want = ref.mla_paged_attention_quant_ref(
        qe, qr, cw, rw, ccb, rcb, tbl, pos, alive, bits=4, kv_lora=LAT,
        rope_dim=RD, scale=scale)
    got = ops.mla_paged_attention_quant(
        qe, qr, cw, rw, ccb, rcb, tbl, pos, alive, bits=4, kv_lora=LAT,
        rope_dim=RD, scale=scale, token_tile=2, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[ALIVE],
                               np.asarray(want)[ALIVE],
                               rtol=2e-5, atol=2e-5)


def test_page_gather_pallas_interpret_bit_exact():
    _, kp, _, tbl, _, alive = _gqa_case()
    want = ref.gather_pages_ref(kp, tbl, alive)
    got = ops.page_gather(kp, tbl, alive, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# dead-slot regression: stale page tables never gather live pages
# ---------------------------------------------------------------------------

def test_gather_masks_dead_slots_to_trash_page():
    _, kp, _, tbl, _, alive = _gqa_case()
    # pool with a recognizable trash page
    kp = kp.at[0].set(0.0)
    for route in (lambda: dispatch.page_gather(kp, tbl, alive,
                                               backend="ref"),
                  lambda: attn._gather_slots(kp, tbl, alive)):
        out = np.asarray(route()).reshape(B, NPG, PAGE, KV, HD)
        # dead slot 2's table points at live pages 4 and 5, but its
        # gathered view must be the trash page
        np.testing.assert_array_equal(out[2], 0.0)
        # alive slots still see exactly their tables' pages
        np.testing.assert_array_equal(out[0, 0], np.asarray(kp)[1])
        np.testing.assert_array_equal(out[1, 1], np.asarray(kp)[0])


# ---------------------------------------------------------------------------
# freeze-on-first-write storage semantics
# ---------------------------------------------------------------------------

def test_write_slot_quant_freezes_codebook_on_first_write():
    bits = 4
    cache = attn.init_quant_paged_kv_cache(NP_POOL, PAGE, KV, HD, bits,
                                           "page", jnp.float32)
    words, cbs = cache.k_words, cache.k_cb
    tbl = jnp.asarray(TBL)
    alive = jnp.asarray([True, True, True])
    v0 = _rand((B, KV, HD), 10)
    # first write lands at offset 0 → fits and freezes the codebook
    pos0 = jnp.asarray([0, 0, 0], jnp.int32)
    w1, c1 = attn._write_slot_quant(words, cbs, tbl, pos0, alive, v0,
                                    PAGE, bits, "page")
    # a later in-page write must reuse the frozen codebook verbatim
    v1 = _rand((B, KV, HD), 11)
    pos1 = jnp.asarray([1, 1, 1], jnp.int32)
    w2, c2 = attn._write_slot_quant(w1, c1, tbl, pos1, alive, v1,
                                    PAGE, bits, "page")
    phys = np.asarray(TBL)[np.arange(B), 0]
    np.testing.assert_array_equal(np.asarray(c2)[phys],
                                  np.asarray(c1)[phys])
    # storage is a pure function of the written values: replay the same
    # writes and the words/codebooks are bit-identical
    w1b, c1b = attn._write_slot_quant(words, cbs, tbl, pos0, alive, v0,
                                      PAGE, bits, "page")
    w2b, c2b = attn._write_slot_quant(w1b, c1b, tbl, pos1, alive, v1,
                                      PAGE, bits, "page")
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(w2b))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(c2b))
    # and the stored rows dequantize to assign-then-lookup of the
    # written values (storage exactness)
    cb_p = np.asarray(c2)[phys]                      # [B, 1, K]
    for b in range(B):
        for off, v in ((0, v0), (1, v1)):
            row = compression.unpack_rows(
                np.asarray(w2)[phys[b], off], HD, 1 << bits)
            want_idx = np.asarray(kvquant.assign_codebook(
                np.asarray(v)[b].reshape(1, 1, -1),
                jnp.asarray(cb_p[b:b + 1]))).reshape(KV, HD)
            np.testing.assert_array_equal(row, want_idx)


# ---------------------------------------------------------------------------
# 4. decode-step distortion bound
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", kvquant.KV_BITS_CHOICES)
def test_decode_step_within_codebook_distortion_bound(bits):
    """Attention over quantized pages vs over the original dense values:
    the output error is bounded by the measured per-scalar codebook
    distortion ε (softmax weights are a convex combination; the value
    term contributes ≤ ε directly, the key term through the bounded
    logit shift).  The bound tightens as kv_bits grows."""
    q, kp, vp, tbl, pos, alive = _gqa_case()
    kw, kcb, kdeq = _quant_pool(kp, bits)
    vw, vcb, vdeq = _quant_pool(vp, bits)
    eps = max(float(jnp.max(jnp.abs(kdeq - kp))),
              float(jnp.max(jnp.abs(vdeq - vp))))
    out_q = np.asarray(ref.paged_attention_quant_ref(
        q, kw, vw, kcb, vcb, tbl, pos, alive, bits=bits, head_dim=HD,
        softcap=None, scale=SCALE))
    out_d = np.asarray(ref.paged_attention_ref(
        q, kp, vp, tbl, pos, alive, softcap=None, scale=SCALE))
    err = np.max(np.abs(out_q - out_d)[ALIVE])
    # ε + (logit-shift sensitivity): |Δlogit| ≤ scale·|q|₁·ε, and the
    # softmax's value spread is O(max|v|); a generous constant covers it
    qmax = float(jnp.max(jnp.abs(q)))
    vmax = float(jnp.max(jnp.abs(vp)))
    bound = eps + 2.0 * SCALE * qmax * HD * KV * eps * vmax
    assert err <= bound, (bits, err, eps, bound)


# ---------------------------------------------------------------------------
# 5. engine differential: {gqa-mixed, mla} × K ∈ {2, 16}
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _packed_arch(arch: str, k: int):
    if arch == "gqa-mixed":
        cfg = mixed_cfg(tie=True)
    else:
        from repro.configs import get_config, reduce_config
        cfg = reduce_config(get_config("deepseek-v2-lite-16b"))
    from repro.models.transformer import init_params
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, pack_model(params, k).serving_params(packed=True)


@functools.lru_cache(maxsize=None)
def _arch_prompts(vocab: int, n: int, length: int):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(7 + length), (n, length), 0, vocab))


def _reqs(cfg, n=3):
    prompts = _arch_prompts(cfg.vocab, n, 16)
    return [Request(rid=r, prompt=prompts[r],
                    max_new_tokens=[5, 3, 4][r % 3]) for r in range(n)]


def _oracle(params, cfg, reqs):
    prompts = np.stack([r.prompt for r in reqs])
    gen = max(r.max_new_tokens for r in reqs)
    toks = np.asarray(greedy_generate(params, cfg,
                                      jnp.asarray(prompts), gen)[0])
    return {r.rid: truncate_at_eos(toks[i][:r.max_new_tokens], r.eos_id)
            for i, r in enumerate(reqs)}


@pytest.mark.parametrize("arch", ["gqa-mixed", "mla"])
@pytest.mark.parametrize("k", [2, 16])
def test_engine_dense_kv_bit_exact_and_quant_kv_deterministic(arch, k):
    cfg, sp = _packed_arch(arch, k)
    reqs = _reqs(cfg)
    want = _oracle(sp, cfg, reqs)

    # kv_bits=0: the dispatch rerouting must not change a single token
    outs = Engine(sp, cfg, n_slots=2, page_size=8,
                  max_seq=24).run([Request(rid=r.rid, prompt=r.prompt,
                                           max_new_tokens=r.max_new_tokens)
                                   for r in reqs])
    assert sorted(outs) == sorted(want)
    for rid in want:
        np.testing.assert_array_equal(
            outs[rid], want[rid],
            err_msg=f"{arch}/K{k}: dense-KV stream != one-shot oracle")

    # kv_bits=4: runs to completion, typed FINISHED, and the streams are
    # a pure function of the requests — identical across reruns and
    # across slot counts (freeze-on-first-write storage determinism)
    runs = []
    for n_slots in (2, 2, 3):
        eng = Engine(sp, cfg, n_slots=n_slots, page_size=8, max_seq=24,
                     kv_bits=4)
        runs.append(eng.run([Request(rid=r.rid, prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens)
                             for r in reqs]))
        assert all(res.ok for res in eng.results.values())
    for rid in runs[0]:
        np.testing.assert_array_equal(runs[0][rid], runs[1][rid])
        np.testing.assert_array_equal(
            runs[0][rid], runs[2][rid],
            err_msg=f"{arch}/K{k}: quantized-KV stream depends on "
                    f"batching")
        assert len(runs[0][rid]) == reqs[rid].max_new_tokens


def test_engine_quant_kv_head_mode_and_kv8():
    """The remaining kv knobs: per-head codebooks and 8-bit pages both
    serve deterministically on the mixed stack."""
    cfg, sp = _packed_arch("gqa-mixed", 16)
    reqs = _reqs(cfg)
    for kwargs in ({"kv_bits": 4, "kv_cb_mode": "head"}, {"kv_bits": 8}):
        a = Engine(sp, cfg, n_slots=2, page_size=8, max_seq=24,
                   **kwargs).run(list(reqs))
        b = Engine(sp, cfg, n_slots=2, page_size=8, max_seq=24,
                   **kwargs).run(list(reqs))
        for rid in a:
            np.testing.assert_array_equal(a[rid], b[rid])


def test_engine_rejects_bad_kv_knobs():
    cfg, sp = _packed_arch("gqa-mixed", 16)
    with pytest.raises(ValueError, match="kv_bits"):
        Engine(sp, cfg, n_slots=2, page_size=8, max_seq=24, kv_bits=3)
    with pytest.raises(ValueError, match="kv_cb_mode"):
        Engine(sp, cfg, n_slots=2, page_size=8, max_seq=24, kv_bits=4,
               kv_cb_mode="tensor")
