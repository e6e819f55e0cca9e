"""The static serving-graph auditor's own test matrix (ISSUE 6).

Two halves:

* **poisoned self-tests** — each checker must flag a graph/config built
  to violate its invariant (a dequant-then-dot graph, an oversized or
  lane-misaligned block config, a shape-varying jit loop).  A linter
  that never fires is indistinguishable from one that works;
* **clean golden runs** — the full audit over both committed fixtures
  passes with zero active violations and byte-exact eq.-14 accounting
  (``bits_per_index(K)/8`` B/weight from compiled HLO).

Everything runs on CPU: jaxpr tracing is abstract eval (no Mosaic), the
HBM compile uses the ref backend, and the VMEM checks are integer
arithmetic over static shapes.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from helpers import packed_tiny
from repro.analysis import audit as audit_mod
from repro.analysis import (RecompileAuditor, RecompileViolation,
                            find_dense_inflations, protected_leaves,
                            validate_block_config)
from repro.analysis.graph import trace_backend
from repro.analysis.vmem import audit_block_space, estimate_vmem_bytes
from repro.analysis.zoo import CONFIGS, infer_config
from repro.core.compression import PackedModel, bits_per_index
from repro.kernels import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")


# ---------------------------------------------------------------------------
# poisoned: dense-inflation detection
# ---------------------------------------------------------------------------

def _tiny_serving():
    cfg, pm = packed_tiny(16, "float32")
    sp = pm.serving_params(packed=True)
    return cfg, sp, protected_leaves(sp)


def test_poisoned_dequant_then_dot_is_flagged():
    """The exact pre-PR-4 LM-head failure: materialize the dense weight
    from the packed operand, then contract — must be flagged, with the
    dot feed proven."""
    _, sp, prot = _tiny_serving()
    mlp = sp["stacks"][0]["pos0"]["mlp"]
    lay = mlp["w_in_layout"]

    def poisoned(p, x):
        m = p["stacks"][0]["pos0"]["mlp"]
        w = dispatch.decode_packed_leaf(m["w_in_pidx"][0],
                                        m["w_in_cb"][0], lay)
        return x @ w

    x = jnp.zeros((4, lay.kd))
    hits = find_dense_inflations(poisoned, (sp, x), prot)
    leaves = {h.leaf for h in hits}
    assert "['stacks'][0]['pos0']['mlp']['w_in']" in leaves
    assert any(h.feeds_dot for h in hits)


def test_clean_fused_route_not_flagged():
    """The production route (pallas backend pinned while tracing) must
    NOT be flagged: the packed operand feeds an opaque pallas_call."""
    _, sp, prot = _tiny_serving()
    mlp = sp["stacks"][0]["pos0"]["mlp"]
    lay = mlp["w_in_layout"]

    def fused(p, x):
        m = p["stacks"][0]["pos0"]["mlp"]
        return dispatch.packed_quantized_matmul(
            x, m["w_in_pidx"][0], m["w_in_cb"][0], layout=lay,
            backend="pallas")

    x = jnp.zeros((4, lay.kd))
    assert find_dense_inflations(fused, (sp, x), prot) == []


def test_taint_disambiguates_same_shape_leaves():
    """Two leaves can share a dense shape; the detector must charge the
    one whose arrays actually feed the gather, not both."""
    _, sp, prot = _tiny_serving()
    mlp = sp["stacks"][0]["pos0"]["mlp"]
    # w_in [32, 64] and w_gate [32, 64] share a shape in tiny_cfg
    assert prot["['stacks'][0]['pos0']['mlp']['w_in']"]["dense_shapes"] \
        == prot["['stacks'][0]['pos0']['mlp']['w_gate']"]["dense_shapes"]

    def poisoned(p, x):
        m = p["stacks"][0]["pos0"]["mlp"]
        w = dispatch.decode_packed_leaf(m["w_gate_pidx"][0],
                                        m["w_gate_cb"][0],
                                        m["w_gate_layout"])
        return x @ w

    x = jnp.zeros((4, mlp["w_gate_layout"].kd))
    leaves = {h.leaf for h in find_dense_inflations(poisoned, (sp, x),
                                                    prot)}
    assert leaves == {"['stacks'][0]['pos0']['mlp']['w_gate']"}


def test_full_model_pallas_trace_clean_on_tiny():
    """tiny_cfg has no MoE → the whole forward must trace clean on the
    production backend (no allowlist needed)."""
    from repro.models.transformer import forward
    cfg, sp, prot = _tiny_serving()
    toks = jnp.zeros((1, 8), jnp.int32)
    with trace_backend("pallas"):
        hits = find_dense_inflations(lambda p, t: forward(p, cfg, t),
                                     (sp, toks), prot)
    assert hits == []


def test_ref_trace_is_flagged():
    """Sanity that the detector fires on the dequant reference route —
    proving the clean pallas result above is not vacuous."""
    from repro.models.transformer import forward
    cfg, sp, prot = _tiny_serving()
    toks = jnp.zeros((1, 8), jnp.int32)
    with trace_backend("ref"):
        hits = find_dense_inflations(lambda p, t: forward(p, cfg, t),
                                     (sp, toks), prot)
    assert len({h.leaf for h in hits}) >= 3


# ---------------------------------------------------------------------------
# poisoned: VMEM / block-config lint
# ---------------------------------------------------------------------------

def test_oversized_block_config_rejected():
    res = validate_block_config("packed_matmul", 512, 2048, 8192,
                                4, 16)
    assert not res["ok"]
    assert any("VMEM" in e for e in res["errors"])


def test_lane_straddling_block_rejected():
    # bits=4 → lanes=8; bk=100 straddles word boundaries
    res = validate_block_config("packed_matmul", 8, 256, 100, 4, 16)
    assert not res["ok"] and any("lanes" in e for e in res["errors"])
    # transposed kd-order packs the OUTPUT axis: bn must divide
    res = validate_block_config("packed_matmul_t", 8, 100, 256, 4, 16,
                                order="kd")
    assert not res["ok"] and any("bn=100" in e for e in res["errors"])
    # row order packs the reduction axis: same bn is fine, bad bk isn't
    assert validate_block_config("packed_matmul_t", 8, 100, 256, 4, 16,
                                 order="row")["ok"]


def test_committed_block_table_is_clean():
    """Every committed autotune entry and every heuristic pick for both
    fixtures' leaves must lint clean — this is the CPU-side stand-in for
    Mosaic compile coverage (documented tpu-marker interaction: these
    checks run without a TPU)."""
    for fx in ("pr2_mlp_only", "pr3_full"):
        pm = PackedModel.load(os.path.join(FIXTURES, fx))
        prot = protected_leaves(pm.serving_params(packed=True))
        res = audit_block_space(prot)
        assert res["violations"] == [], (fx, res["violations"])
        assert res["rows"], fx


def test_prefill_block_lint_poisoned_and_clean():
    """The blockwise-prefill token-tile lint: an oversized tile must be
    rejected (a lint that never fires proves nothing), and the committed
    dispatch table must sweep clean at every supported kv width."""
    from repro.analysis.vmem import (audit_prefill_block_space,
                                     validate_prefill_block_config)
    bad = validate_prefill_block_config("dense", 128, 4096)
    assert not bad["ok"] and any("VMEM" in e for e in bad["errors"])
    assert not validate_prefill_block_config("nope", 12, 8)["ok"]
    assert not validate_prefill_block_config("quant", 12, 8, bits=3)["ok"]
    assert validate_prefill_block_config("quant", 12, 8, bits=4)["ok"]
    swept = audit_prefill_block_space()
    assert swept["rows"] and swept["violations"] == []
    assert {r["kind"] for r in swept["rows"]} == {"dense", "quant"}


def test_vmem_estimate_monotone_in_blocks():
    small = estimate_vmem_bytes("packed_matmul", 8, 128, 512, 4, 16)
    big = estimate_vmem_bytes("packed_matmul", 128, 512, 2048, 4, 16)
    assert 0 < small < big


# ---------------------------------------------------------------------------
# poisoned: recompile gate
# ---------------------------------------------------------------------------

def test_shape_varying_jit_trips_auditor():
    jf = jax.jit(lambda x: x * 2)
    jf(jnp.zeros((4,)))                     # warm one shape
    auditor = RecompileAuditor({"f": jf})
    auditor.snapshot()
    jf(jnp.zeros((4,)))                     # same shape: no growth
    assert auditor.check("same-shape") == {"f": 0}
    jf(jnp.zeros((8,)))                     # new shape: retrace
    with pytest.raises(RecompileViolation, match="f: \\+1"):
        auditor.check("shape-varying loop")
    # an explicit budget documents legitimate first-compiles
    assert auditor.check("budgeted", budget={"f": 1}) == {"f": 1}


def test_frozen_context_raises_on_growth():
    jf = jax.jit(lambda x: x + 1)
    auditor = RecompileAuditor({"f": jf})
    with pytest.raises(RecompileViolation):
        with auditor.frozen("cold jit"):
            jf(jnp.zeros((3,)))


# ---------------------------------------------------------------------------
# allowlist semantics
# ---------------------------------------------------------------------------

def test_allowlist_glob_matches_bracketed_paths():
    allow = [{"check": "dense-inflation", "subject": "*['experts_w_*']",
              "reason": "einsum operand"}]
    v_moe = {"check": "dense-inflation",
             "subject": "['stacks'][1]['pos0']['mlp']['experts_w_out']",
             "detail": "d"}
    v_mlp = {"check": "dense-inflation",
             "subject": "['stacks'][0]['pos0']['mlp']['w_out']",
             "detail": "d"}
    v_hbm = {"check": "hbm-bytes", "subject": v_moe["subject"],
             "detail": "d"}
    active, allowed = audit_mod.split_allowed([v_moe, v_mlp, v_hbm],
                                              allow)
    assert [v["subject"] for v in allowed] == [v_moe["subject"]]
    assert len(active) == 2
    assert allowed[0]["allowed_reason"] == "einsum operand"


def test_allowlist_entry_requires_reason(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text('{"entries": [{"check": "*", "subject": "*"}]}')
    with pytest.raises(ValueError, match="reason"):
        audit_mod.load_allowlist(str(p))


def test_packaged_allowlist_loads():
    entries = audit_mod.load_allowlist()
    assert all(e["reason"] for e in entries)


# ---------------------------------------------------------------------------
# clean golden runs — the CI gate over the committed fixtures
# ---------------------------------------------------------------------------

def test_zoo_infers_fixture_configs():
    for fx, want in (("pr2_mlp_only", "tiny"), ("pr3_full", "mixed")):
        pm = PackedModel.load(os.path.join(FIXTURES, fx))
        key, cfg = infer_config(pm)
        assert key == want
    with pytest.raises(ValueError, match="unknown config"):
        infer_config(pm, "nope")
    assert set(CONFIGS) == {"tiny", "tiny-untied", "mixed", "mixed-tied"}


@pytest.mark.parametrize("fixture,skip", [
    ("pr2_mlp_only", []),
    # recompile scenario is fixture-independent (same engine loop);
    # covered once above to bound suite runtime
    ("pr3_full", ["recompile"]),
])
def test_golden_fixture_audits_clean(fixture, skip):
    report = audit_mod.run_audit(os.path.join(FIXTURES, fixture),
                                 skip=skip)
    assert report["ok"], report["violations"]
    assert report["violations"] == []
    hbm = report["checks"]["hbm"]
    assert set(hbm) == {"forward", "prefill", "decode_step_slots",
                        "engine_decode_sample",
                        "engine_decode_sample_kvq4",
                        "engine_prefill_chunk"}
    for entry, res in hbm.items():
        assert res["rows"], entry
        for row in res["rows"]:
            exact = bits_per_index(row["k"]) / 8
            assert row["bytes_per_weight"] == exact, row
            assert row["uses"] >= 1, row
    # every protected leaf is covered in every entry's byte audit
    n_leaves = len(report["protected_leaves"])
    for entry, res in hbm.items():
        assert len(res["rows"]) == n_leaves, entry
    # the KV-page operand check engaged: the quantized-KV decode reads
    # live uint32 word pools (zero unexplained dense-width KV reads —
    # those would be violations, asserted empty above)
    kvq = hbm["engine_decode_sample_kvq4"]
    assert kvq["kv_rows"] and kvq["kv_word_input_bytes"] > 0
    for row in kvq["kv_rows"]:
        assert row["uses"] >= 1, row
        assert row["hbm_bytes"] < row["dense_bytes"], row
    # the paged + blockwise-prefill autotune tables are swept by the
    # vmem lint
    assert report["checks"]["vmem"]["paged_configs_checked"] >= 1
    assert report["checks"]["vmem"]["prefill_configs_checked"] >= 1
    if "recompile" not in skip:
        ev = report["checks"]["recompile"]["events"]
        assert ev["preemptions"] >= 1 and ev["finished"] >= 3
    # MoE exceptions surface as *allowed*, never silently dropped
    if fixture == "pr3_full":
        assert all("experts_w_" in v["subject"]
                   for v in report["allowed_violations"])
        assert report["allowed_violations"]


# ---------------------------------------------------------------------------
# satellite: bench group validation
# ---------------------------------------------------------------------------

def _run_bench(*argv):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)


def test_bench_unknown_group_errors():
    res = _run_bench("--only", "nosuchgroup")
    assert res.returncode == 2
    assert "nosuchgroup" in res.stderr
    assert "kernels" in res.stderr and "engine" in res.stderr


def test_bench_mixed_valid_invalid_tokens_error():
    res = _run_bench("--only", "kernels,typo")
    assert res.returncode == 2 and "typo" in res.stderr


def test_audit_table_renders_recompile_counts():
    """The human audit table must render whatever jit-cache counters the
    engine's ``trace_counts()`` reports — it used to hard-code the
    pre-blockwise key set (``prefill``/``commit``) and KeyError'd on
    real reports after the rename to ``prefill_chunk``."""
    from repro.launch.report import audit_table

    report = {
        "artifact": "x", "config": "mixed", "passed": True,
        "checks": {"recompile": {
            "events": {"steps": 9, "admitted": 3, "finished": 3,
                       "preemptions": 1},
            "counts": {"decode": 1, "prefill_chunk": 2, "sample": 1},
        }},
    }
    table = audit_table(report)
    assert "prefill_chunk=2" in table and "decode=1" in table
