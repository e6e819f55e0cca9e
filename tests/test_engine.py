"""Continuous-batching engine differential + stress suite.

THE invariant: for any admission order, slot count, page-pool size and
completion pattern, every request's greedy token stream from the engine
equals the one-shot lockstep loop's (``repro.engine.oneshot``) — across
{dense, packed} serving layouts and K ∈ {2, 16} on the mixed
gqa+moe+ssm stack.  Plus: page-reuse stress (short/long interleave with
an oversubscribed pool never corrupts a neighbor's KV), no-recompile on
admission, deterministic per-request sampling, and scheduler / page-pool
unit behavior.
"""
import functools

import jax
import numpy as np
import pytest

from helpers import mixed_cfg, pack_model
from repro.engine import (Engine, Outcome, PagePool, Request,
                          SlotScheduler, greedy_generate, truncate_at_eos)


@functools.lru_cache(maxsize=None)
def _mixed(k: int, layout: str):
    """(cfg, serving params) for the mixed gqa+moe+ssm stack — cached:
    packing is the expensive step."""
    cfg = mixed_cfg(tie=True)
    params = jax.random.PRNGKey(0)
    from repro.models.transformer import init_params
    params = init_params(jax.random.PRNGKey(0), cfg)
    if layout == "dense":
        return cfg, params
    packed = pack_model(params, k)
    return cfg, packed.serving_params(packed=True)


@functools.lru_cache(maxsize=None)
def _prompts(vocab: int, n: int, length: int):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(7 + length), (n, length), 0, vocab))


def _oracle(params, cfg, reqs, block=None):
    """One-shot greedy streams per request (grouped by prompt length —
    the lockstep loop needs a rectangular prompt batch).  ``block`` is
    the prefill block size; pass the engine's ``effective_chunk`` when
    it differs from the default so both sides run the same blockwise
    partition (different partitions are numerically inequivalent)."""
    out = {}
    by_len = {}
    for r in reqs:
        by_len.setdefault(r.prompt_len, []).append(r)
    for length, group in by_len.items():
        prompts = np.stack([r.prompt for r in group])
        gen = max(r.max_new_tokens for r in group)
        toks = np.asarray(greedy_generate(params, cfg,
                                          jax.numpy.asarray(prompts),
                                          gen, block=block)[0])
        for i, r in enumerate(group):
            out[r.rid] = truncate_at_eos(toks[i][:r.max_new_tokens],
                                         r.eos_id)
    return out


def _assert_streams_equal(outs, want):
    assert set(outs) == set(want)
    for rid in want:
        np.testing.assert_array_equal(
            outs[rid], want[rid],
            err_msg=f"request {rid}: engine stream != one-shot stream")


# ---------------------------------------------------------------------------
# The differential matrix: {dense, packed} × K ∈ {2, 16}, staggered
# admission (more requests than slots, mixed prompt lengths) and
# out-of-order completion (mixed max-new-tokens)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout,k", [("dense", 16), ("packed", 2),
                                      ("packed", 16)])
def test_engine_matches_one_shot_staggered(layout, k):
    cfg, params = _mixed(k, layout)
    p16 = _prompts(cfg.vocab, 4, 16)
    p8 = _prompts(cfg.vocab, 2, 8)
    gens = [6, 2, 5, 3, 6, 1]
    reqs = [Request(rid=r, prompt=(p16[r // 2] if r % 2 == 0
                                   else p8[r // 4]),
                    max_new_tokens=gens[r]) for r in range(6)]
    # token_budget 12 < prompt 16: the engine prefills in blocks of 12
    # ({12, 4} for the long prompts, {8} for the short) — the oracle
    # must run the same partition
    want = _oracle(params, cfg, reqs, block=12)

    eng = Engine(params, cfg, n_slots=2, page_size=8, max_seq=24,
                 token_budget=12)
    assert eng.effective_chunk == 12
    # Staggered admission / eviction never retraces: jit-cache growth is
    # bounded by the number of distinct *shapes* (decode: 1 config;
    # prefill chunks: the 3 distinct block widths 12/4/8; sample: 1),
    # never by admission or completion events.
    from repro.analysis import RecompileAuditor
    auditor = RecompileAuditor(eng.trace_counts)
    with auditor.frozen("staggered admission/completion",
                        budget={"decode": 1, "prefill_chunk": 3,
                                "sample": 1}):
        outs = eng.run(reqs)
    _assert_streams_equal(outs, want)
    s = eng.stats.summary()
    assert s["finished"] == 6
    assert 0 < s["slot_occupancy"] <= 1
    assert 0 < s["page_utilization_max"] <= 1


def test_engine_eos_early_exit_out_of_order():
    """EOS stops a request mid-stream; its slot and pages free while
    neighbors keep decoding."""
    cfg, params = _mixed(16, "packed")
    p16 = _prompts(cfg.vocab, 3, 16)
    base = [Request(rid=r, prompt=p16[r], max_new_tokens=8)
            for r in range(3)]
    plain = _oracle(params, cfg, base)
    # request 1's EOS: the first token at index >= 2 (and before the
    # last) that does not occur earlier in its greedy stream, so the
    # stream stops just after it, mid-stream
    s = [int(t) for t in plain[1]]
    cut = next(i for i in range(2, len(s) - 1) if s[i] not in s[:i])
    eos = s[cut]
    reqs = [Request(rid=r, prompt=p16[r], max_new_tokens=8,
                    eos_id=eos if r == 1 else None) for r in range(3)]
    want = _oracle(params, cfg, reqs)
    assert len(want[1]) == cut + 1

    eng = Engine(params, cfg, n_slots=2, page_size=8, max_seq=24)
    outs = eng.run(reqs)
    _assert_streams_equal(outs, want)
    assert len(outs[1]) == cut + 1 and outs[1][-1] == eos
    assert len(outs[0]) == len(outs[2]) == 8


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b",
                                  "recurrentgemma-2b"])
def test_engine_matches_one_shot_mla_rglru_windowed(arch):
    """The mixer kinds the mixed stack doesn't cover: MLA (paged
    absorbed-latent decode) and RG-LRU + sliding-window gqa_local
    (per-slot ring buffers) — engine streams must still equal the
    one-shot loop's under staggered admission."""
    from repro.configs import get_config, reduce_config
    from repro.models.transformer import init_params
    cfg = reduce_config(get_config(arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg.vocab, 3, 16)
    reqs = [Request(rid=r, prompt=prompts[r],
                    max_new_tokens=[5, 3, 4][r]) for r in range(3)]
    want = _oracle(params, cfg, reqs)
    eng = Engine(params, cfg, n_slots=2, page_size=8, max_seq=24)
    _assert_streams_equal(eng.run(reqs), want)


# ---------------------------------------------------------------------------
# Blockwise prefill: prompt_len >> prefill_chunk
# ---------------------------------------------------------------------------

_LONG_GEO = dict(page_size=8, max_seq=64, prefill_chunk=8, token_budget=10)


def _long_reqs(cfg, n=3, length=40):
    prompts = _prompts(cfg.vocab, n, length)
    return [Request(rid=r, prompt=prompts[r],
                    max_new_tokens=[6, 3, 5][r % 3]) for r in range(n)]


@pytest.mark.parametrize("layout,k,kv_bits", [
    ("dense", 16, 0), ("packed", 2, 0), ("packed", 16, 0),
    ("dense", 16, 4), ("packed", 16, 4)])
def test_blockwise_prefill_long_prompt(layout, k, kv_bits):
    """prompt_len (40) >> prefill_chunk (8): prefill streams through the
    prompt in 5 real block forwards per request — recurrent/window
    carries cross block boundaries, each block's K/V lands in the slot's
    pages (quantized when kv_bits > 0) — and the final streams still
    equal the oracle.  Plus the stats identities the old commit-style
    prefill lied about."""
    cfg, params = _mixed(k, layout)
    reqs = _long_reqs(cfg)
    kvq = dict(kv_bits=kv_bits, kv_cb_mode="page") if kv_bits else {}
    eng = Engine(params, cfg, n_slots=2, **_LONG_GEO, **kvq)
    assert eng.effective_chunk == 8
    outs = eng.run(list(reqs))
    if kv_bits == 0:
        want = _oracle(params, cfg, reqs, block=8)
        _assert_streams_equal(outs, want)
    else:
        # quantized KV has no dense oracle; the contract (PR 8) is slot
        # -layout invariance: a different slot count means different
        # pages, admission order and preemption pattern — same streams
        outs2 = Engine(params, cfg, n_slots=3, **_LONG_GEO,
                       **kvq).run(list(reqs))
        _assert_streams_equal(outs, outs2)
    st = eng.stats
    assert st.prefill_tokens == 3 * 40          # computed, not charged
    assert st.prefill_calls == 3 * 5            # ceil(40/8) blocks each
    assert st.prefill_samples == 3
    assert st.generated_tokens == st.decode_tokens + st.prefill_samples
    assert st.generated_tokens == sum(len(v) for v in outs.values())


@pytest.mark.parametrize("kv_bits", [0, 4])
def test_blockwise_prefill_long_prompt_mla(kv_bits):
    """Same long-prompt regime on the MLA stack (absorbed-latent paged
    decode + latent-page blockwise prefill)."""
    from repro.configs import get_config, reduce_config
    from repro.models.transformer import init_params
    cfg = reduce_config(get_config("deepseek-v2-lite-16b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = _long_reqs(cfg)
    kvq = dict(kv_bits=kv_bits, kv_cb_mode="page") if kv_bits else {}
    eng = Engine(params, cfg, n_slots=2, **_LONG_GEO, **kvq)
    outs = eng.run(list(reqs))
    if kv_bits == 0:
        _assert_streams_equal(outs, _oracle(params, cfg, reqs, block=8))
    else:
        outs2 = Engine(params, cfg, n_slots=3, **_LONG_GEO,
                       **kvq).run(list(reqs))
        _assert_streams_equal(outs, outs2)
    assert eng.stats.prefill_calls == 3 * 5


def test_prefill_budget_bounds_compute():
    """THE tentpole claim, asserted on the actual device-call trace: no
    engine step runs a forward over more than ``effective_chunk`` prompt
    tokens — the old engine charged budget per chunk but then ran ONE
    full-prompt forward at commit, so its widest call was prompt_len."""
    cfg, params = _mixed(16, "dense")
    reqs = _long_reqs(cfg)
    eng = Engine(params, cfg, n_slots=2, **_LONG_GEO)
    widths = []
    orig = eng._chunk

    def spy(p, c, caches, table, tok, slot, start):
        widths.append(int(tok.shape[1]))
        return orig(p, c, caches, table, tok, slot, start)

    eng._chunk = spy
    outs = eng.run(list(reqs))
    assert widths, "prefill never ran"
    assert max(widths) <= eng.effective_chunk == 8
    assert sum(widths) == 3 * 40               # every prompt token once
    _assert_streams_equal(outs, _oracle(params, cfg, reqs, block=8))


# ---------------------------------------------------------------------------
# Page reuse stress: oversubscribed pool, short/long interleave
# ---------------------------------------------------------------------------

def test_page_reuse_stress_never_corrupts_neighbor_kv():
    """A long-running request decodes while short requests churn through
    the slots around it, constantly recycling pages.  The pool is
    oversubscribed (stalls + preemptions must occur), yet every stream —
    including the long neighbor's — stays exactly the one-shot stream:
    a page handed to a new request is never still referenced by an old
    page table."""
    cfg, params = _mixed(16, "packed")
    p16 = _prompts(cfg.vocab, 8, 16)
    p8 = _prompts(cfg.vocab, 4, 8)
    reqs = [Request(rid=0, prompt=p16[0], max_new_tokens=8)]  # the long one
    for r in range(1, 8):
        reqs.append(Request(rid=r, prompt=(p8[r % 4] if r % 2
                                           else p16[r]),
                            max_new_tokens=2 + r % 3))
    want = _oracle(params, cfg, reqs)

    # 3 slots but only 7 usable pages (full residency would need 9)
    eng = Engine(params, cfg, n_slots=3, page_size=8, max_seq=24,
                 n_pages=7, token_budget=20)
    outs = eng.run(reqs)
    _assert_streams_equal(outs, want)
    s = eng.stats.summary()
    assert s["page_utilization_max"] > 0.8


def test_preemption_replays_request_exactly():
    """When every runnable slot is page-starved the youngest is
    preempted and replayed from scratch — deterministically, so its
    final stream is still the oracle stream."""
    cfg, params = _mixed(16, "packed")
    p16 = _prompts(cfg.vocab, 6, 16)
    reqs = [Request(rid=r, prompt=p16[r], max_new_tokens=[6, 2, 5, 3, 6,
                                                          4][r])
            for r in range(6)]
    want = _oracle(params, cfg, reqs)
    eng = Engine(params, cfg, n_slots=3, page_size=8, max_seq=22,
                 n_pages=6, token_budget=20)
    outs = eng.run(reqs)
    _assert_streams_equal(outs, want)
    assert eng.stats.preemptions > 0
    assert eng.stats.stall_events > 0


# ---------------------------------------------------------------------------
# Per-slot sampling
# ---------------------------------------------------------------------------

def test_sampled_streams_deterministic_across_batching():
    """temperature/top-k streams depend only on (request, seed), not on
    slot assignment, admission order, or pool shape."""
    cfg, params = _mixed(16, "packed")
    p16 = _prompts(cfg.vocab, 4, 16)

    def mk():
        return [Request(rid=r, prompt=p16[r], max_new_tokens=5,
                        temperature=0.8, top_k=7, seed=100 + r)
                for r in range(4)]

    o1 = Engine(params, cfg, n_slots=2, page_size=8, max_seq=24).run(mk())
    o2 = Engine(params, cfg, n_slots=4, page_size=4, max_seq=24).run(mk())
    for r in o1:
        np.testing.assert_array_equal(o1[r], o2[r])
    # all sampled ids are valid vocab entries
    for r in o1:
        assert (o1[r] >= 0).all() and (o1[r] < cfg.vocab).all()


def test_bf16_model_infers_bf16_pool_and_matches_oracle():
    """The KV-pool dtype is inferred from the embedding leaf: a bf16
    model gets a bf16 pool (an f32 pool would round differently than
    the oracle's bf16 caches and break stream parity)."""
    import jax.numpy as jnp
    from repro.models.transformer import (LayerKind, ModelConfig,
                                          StackSpec, init_params)
    cfg = ModelConfig(
        name="bf16-eng", family="dense", d_model=32, n_heads=4, n_kv=2,
        head_dim=8, d_ff=64, vocab=96,
        stacks=(StackSpec(pattern=(LayerKind("gqa", "dense"),),
                          groups=2),),
        tie_embeddings=True, q_chunk=8, kv_chunk=8, remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    prompts = _prompts(cfg.vocab, 2, 16)
    reqs = [Request(rid=r, prompt=prompts[r], max_new_tokens=[5, 3][r])
            for r in range(2)]
    want = _oracle(params, cfg, reqs)
    eng = Engine(params, cfg, n_slots=2, page_size=8, max_seq=24)
    assert eng.caches[0]["pos0"].k.dtype == jnp.bfloat16
    _assert_streams_equal(eng.run(reqs), want)


def test_top_k_ties_keep_exactly_k():
    """Tie-heavy top-k: exactly k candidates survive the cutoff, ties
    breaking toward the lower token id.  The old ``logits >= cutoff``
    mask kept *every* token tied with the k-th — on flat logits top_k=3
    silently became full-vocab sampling."""
    import jax.numpy as jnp
    from repro.engine import sampling

    v = 16
    flat = jnp.zeros((v,), jnp.float32)        # all 16 logits tied
    for k in (1, 3, 7):
        seen = {int(sampling._sample_one(
            flat, jnp.float32(1.0), jnp.int32(k), sampling.slot_key(s, 0)))
            for s in range(100)}
        assert seen <= set(range(k)), (k, sorted(seen))
        if k > 1:
            assert len(seen) > 1               # still samples within top-k
    # partial tie exactly at the cutoff: k=3 over [5, 5, 3, 3, 3, ...]
    # keeps ids {0, 1} and exactly ONE of the tied 3s — id 2
    lg = jnp.asarray([5.0, 5.0, 3.0, 3.0, 3.0, 1.0, 0.0, -1.0])
    seen = {int(sampling._sample_one(
        lg, jnp.float32(0.7), jnp.int32(3), sampling.slot_key(s, 1)))
        for s in range(200)}
    assert seen <= {0, 1, 2}, sorted(seen)
    # batch wrapper agrees (same mask per row)
    toks = sampling.sample_tokens(
        jnp.stack([lg, lg]), jnp.asarray([0.7, 0.7], jnp.float32),
        jnp.asarray([3, 3], jnp.int32),
        jnp.stack([sampling.slot_key(0, 0), sampling.slot_key(0, 0)]))
    assert int(toks[0]) == int(toks[1]) and int(toks[0]) in (0, 1, 2)


def test_greedy_requests_ignore_seed():
    cfg, params = _mixed(16, "packed")
    p16 = _prompts(cfg.vocab, 2, 16)
    a = Engine(params, cfg, n_slots=2, page_size=8, max_seq=24).run(
        [Request(rid=0, prompt=p16[0], max_new_tokens=4, seed=1)])
    b = Engine(params, cfg, n_slots=2, page_size=8, max_seq=24).run(
        [Request(rid=0, prompt=p16[0], max_new_tokens=4, seed=2)])
    np.testing.assert_array_equal(a[0], b[0])


# ---------------------------------------------------------------------------
# Scheduler / page-pool units
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_accounting():
    pool = PagePool(n_pages=6, page_size=8, n_slots=2,
                    max_pages_per_slot=3)
    assert pool.free_pages == 6 and pool.used_pages == 0
    assert pool.alloc(0, 2)
    assert pool.table[0, 0] != 0 and pool.table[0, 1] != 0
    assert pool.table[0, 2] == 0                 # unallocated → trash
    assert pool.ensure(0, 17)                    # pos 17 → 3rd page
    assert pool.used_pages == 3
    assert not pool.ensure(0, 24)                # beyond max_pages_per_slot
    assert pool.alloc(1, 3)
    assert pool.free_pages == 0
    assert not pool.alloc(0, 1) and not pool.alloc(1, 1)
    freed = pool.free_slot(0)
    assert freed == 3 and pool.free_pages == 3
    assert (pool.table[0] == 0).all()
    # freed pages immediately reusable — and all-or-nothing alloc
    assert not pool.alloc(1, 4)
    p1_before = pool.pages_of(1)
    assert pool.pages_of(1) == p1_before
    pool2 = PagePool(n_pages=3, page_size=8, n_slots=1,
                     max_pages_per_slot=3)
    assert not pool2.alloc(0, 4)
    assert pool2.free_pages == 3


def test_page_pool_seized_pages_not_counted_used():
    """Chaos-seized pages are *withheld*, not owned: they must not
    inflate ``used_pages``/``utilization()`` (the old accounting counted
    a pressure spike as KV residency, so a pool with zero live slots
    could report 100% utilization)."""
    pool = PagePool(n_pages=6, page_size=8, n_slots=2,
                    max_pages_per_slot=3)
    assert pool.alloc(0, 2)
    taken = pool.seize(3)
    assert taken == 3
    assert pool.used_pages == 2                 # live slots only
    assert pool.seized == 3
    assert pool.free_pages == 1
    assert pool.utilization() == pytest.approx(2 / 6)
    # allocator still treats seized pages as unavailable
    assert not pool.alloc(1, 2)
    pool.release()
    assert pool.seized == 0 and pool.free_pages == 4
    assert pool.used_pages == 2
    # seize everything with no live slots: utilization stays 0, not 1
    pool2 = PagePool(n_pages=4, page_size=8, n_slots=1,
                     max_pages_per_slot=4)
    assert pool2.seize(4) == 4
    assert pool2.used_pages == 0
    assert pool2.utilization() == 0.0


def test_slot_scheduler_admit_evict_tracking():
    sched = SlotScheduler(2)
    r = Request(rid=0, prompt=np.arange(5), max_new_tokens=3)
    sched.submit(r)
    assert sched.has_work() and sched.free_ids() == [0, 1]
    st = sched.admit(0, sched.queue.popleft())
    assert sched.free_ids() == [1] and sched.running_ids() == []
    assert sched.prefilling_ids() == [0]
    st.prefilled = True
    st.out.append(42)
    assert sched.running_ids() == [0]
    assert st.write_pos == 5          # prompt_len + n_generated - 1
    assert not st.finished()
    st.out += [43, 44]
    assert st.finished()              # max_new_tokens reached
    sched.evict(0)
    assert not sched.has_work()
    # EOS completion
    r2 = Request(rid=1, prompt=np.arange(4), max_new_tokens=10, eos_id=9)
    st2 = sched.admit(1, r2)
    st2.prefilled = True
    st2.out.append(9)
    assert st2.finished()
    with pytest.raises(ValueError):
        Request(rid=2, prompt=np.array([], np.int32))
    with pytest.raises(ValueError):
        Request(rid=3, prompt=np.arange(3), max_new_tokens=0)


def test_engine_rejects_oversized_request_and_tiny_pool():
    # rejections are *typed outcomes*, not exceptions: submit never
    # raises, never reserves pages, and records the reason
    cfg, params = _mixed(16, "packed")
    p16 = _prompts(cfg.vocab, 1, 16)
    eng = Engine(params, cfg, n_slots=1, page_size=8, max_seq=24)
    out = eng.submit(Request(rid=0, prompt=p16[0], max_new_tokens=100))
    assert out is Outcome.REJECTED_TOO_LARGE
    assert eng.results[0].outcome is Outcome.REJECTED_TOO_LARGE
    assert "max_seq" in eng.results[0].detail
    assert eng.pool.used_pages == 0 and not eng.sched.has_work()
    # a request that fits max_seq but can never fit the pool must be
    # rejected up front (it would otherwise preempt-cycle forever)
    eng2 = Engine(params, cfg, n_slots=1, page_size=8, max_seq=24,
                  n_pages=2)
    out2 = eng2.submit(Request(rid=0, prompt=p16[0], max_new_tokens=8))
    assert out2 is Outcome.REJECTED_TOO_LARGE
    assert "pool" in eng2.results[0].detail
    # pool smaller than one prompt: same typed rejection, not a hang,
    # and run() completes returning no streams
    eng3 = Engine(params, cfg, n_slots=1, page_size=8, max_seq=24,
                  n_pages=1)
    outs = eng3.run([Request(rid=0, prompt=p16[0], max_new_tokens=2)])
    assert outs == {}
    assert eng3.results[0].outcome is Outcome.REJECTED_TOO_LARGE
    assert eng3.stats.rejected == 1


def test_engine_backpressure_and_cancel():
    cfg, params = _mixed(16, "packed")
    prompts = _prompts(cfg.vocab, 5, 8)
    eng = Engine(params, cfg, n_slots=1, page_size=8, max_seq=16,
                 queue_limit=2)
    reqs = [Request(rid=r, prompt=prompts[r], max_new_tokens=4)
            for r in range(5)]
    outcomes = [eng.submit(r) for r in reqs]
    # slot admission happens inside step(), so the limit bounds the
    # whole backlog: 2 queued, 3 shed with a typed outcome
    assert outcomes[:2] == [None, None]
    assert all(o is Outcome.REJECTED_BACKPRESSURE for o in outcomes[2:])
    # cancel one queued request before it ever runs
    assert eng.cancel(1)
    assert eng.results[1].outcome is Outcome.CANCELLED
    assert not eng.cancel(99)          # unknown rid
    outs = eng.run()
    assert sorted(outs) == [0]
    assert eng.results[0].outcome is Outcome.FINISHED
    assert eng.stats.cancelled == 1 and eng.stats.rejected == 3
    # every submitted rid has exactly one typed outcome
    assert sorted(eng.results) == [0, 1, 2, 3, 4]


def test_engine_deadline_exceeded_typed():
    cfg, params = _mixed(16, "packed")
    prompts = _prompts(cfg.vocab, 2, 8)
    eng = Engine(params, cfg, n_slots=2, page_size=8, max_seq=64)
    eng.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=40,
                       deadline_steps=4))
    eng.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=4))
    outs = eng.run()
    # the tight-deadline request expires mid-stream with partial tokens
    # and freed pages; its neighbor finishes untouched
    assert sorted(outs) == [1]
    res = eng.results[0]
    assert res.outcome is Outcome.DEADLINE_EXCEEDED
    assert 0 < res.tokens.size < 40
    assert eng.results[1].outcome is Outcome.FINISHED
    assert eng.pool.used_pages == 0
    assert eng.stats.deadline_expired == 1


def test_engine_max_steps_returns_partials():
    cfg, params = _mixed(16, "packed")
    prompts = _prompts(cfg.vocab, 2, 8)
    eng = Engine(params, cfg, n_slots=2, page_size=8, max_seq=64)
    reqs = [Request(rid=r, prompt=prompts[r], max_new_tokens=30)
            for r in range(2)]
    outs = eng.run(reqs, max_steps=6)
    # overrun no longer throws away completed work: stragglers fail
    # typed with their partial prefix attached
    assert outs == {}                  # nothing finished in 6 steps
    for r in range(2):
        res = eng.results[r]
        assert res.outcome is Outcome.FAILED
        assert "max_steps" in res.detail
        assert res.tokens.size > 0
    assert not eng.sched.has_work() and eng.pool.used_pages == 0
