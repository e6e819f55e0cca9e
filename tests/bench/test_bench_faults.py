"""A whole run of the harness on the CPU at a tiny size, past its look
for a chip: a sound run is correct, and a run whose timed path is broken
underneath reads ``correct: false``, once for each fault a served cell
can have.  (The cells run on one chip, so no exchange between chips can
be left out.)"""
import time

import jax.numpy as jnp
import pytest

import bench_tiny
from harness import cell as C

SECONDS = 2.0


def _run(monkeypatch, fault=None, kv_bits=0, mix=bench_tiny.MIX):
    monkeypatch.setattr(C, "check_model",
                        lambda spec, model: bench_tiny.program_config())
    hooks = {}
    if fault is not None:
        installed = set()

        def step(eng):
            if id(eng) not in installed:
                installed.add(id(eng))
                broken = FAULTS[fault](eng._decode)
                broken._cache_size = eng._decode._cache_size
                eng._decode = broken
            return eng.step()
        hooks["step"] = step
    return C.run_cell({"name": "tiny.chat", "chips": 1},
                      bench_tiny.config(kv_bits=kv_bits), mix,
                      2**33 + 11, SECONDS, False, time.perf_counter(),
                      require_tpu=False, hooks=hooks, log=lambda m: None)


def _state_unchanged(decode):
    """The decode step hands back the caches it was given."""
    def fn(params, cfg, caches, *args):
        nxt, bad, _ = decode(params, cfg, caches, *args)
        return nxt, bad, caches
    return fn


def _half_batch(decode):
    """The upper half of the live slots (all of them when one is live)
    is decoded without its input token."""
    def fn(params, cfg, caches, table, tokens, pos, alive, *args):
        rank = jnp.cumsum(alive) - 1
        out = alive & (rank >= jnp.sum(alive) // 2)
        return decode(params, cfg, caches, table,
                      jnp.where(out[:, None], 0, tokens), pos, alive, *args)
    return fn


def _token_altered(decode):
    """Every sampled token is replaced by its neighbour in the vocab."""
    def fn(params, cfg, caches, *args):
        nxt, bad, new = decode(params, cfg, caches, *args)
        return (nxt + 1) % cfg.vocab, bad, new
    return fn


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


@pytest.mark.parametrize("kv_bits,mix", [(0, bench_tiny.MIX),
                                         (4, bench_tiny.FOUR_CLIENT_MIX)])
def test_sound_run_is_correct(monkeypatch, kv_bits, mix):
    out = _run(monkeypatch, kv_bits=kv_bits, mix=mix)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert {"setup_s", "itl_p50_ms", "output_tok_s"} <= set(out["metrics"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_incorrect(monkeypatch, fault):
    out = _run(monkeypatch, fault)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
