"""The benchmark's byte counts agree with what the program is proved to
read: the packed-matmul bytes are the eq.-14 bytes that
``repro.analysis.hbm`` finds as entry parameters of the compiled step."""
import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from harness import client, work, weights
from harness.spec import ModelSpec


@pytest.mark.parametrize("k", [2, 16])
def test_packed_bytes_equal_the_audited_hbm_bytes(k):
    from repro.analysis.graph import protected_leaves
    from repro.analysis.hbm import audit_entry_hbm
    from repro.models.transformer import forward

    cfg = bench_tiny.program_config()
    spec = ModelSpec.from_config(bench_tiny.config(k=k))
    tree = weights.serving_tree(weights.seed_key(1), spec)
    tokens = jnp.zeros((1, 8), jnp.int32)
    audit = audit_entry_hbm(lambda p, t: forward(p, cfg, t), (tree, tokens),
                            protected_leaves(tree))
    assert not audit["violations"]
    proved = {r["path"].split("['")[-1][:-2]: r["hbm_bytes"]
              for r in audit["rows"]}
    names = ["wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"]
    for name, (rows, cols) in zip(names, work.layer_matrices(spec)):
        words = work.matrix_bytes(spec, rows, cols) - spec.k * 4
        assert spec.layers * words == proved[name], name
    v, d = spec.vocab, spec.d_model
    assert work.matrix_bytes(spec, v, d) - spec.k * 4 == proved["embed_tok"]


def test_counts_follow_the_tokens_served():
    spec = ModelSpec.from_config(bench_tiny.config())
    one = client.Step(0, 1, 1, [10], [])
    three = client.Step(0, 1, 3, [10, 20, 30], [])
    f1, b1 = work.packed_work(spec, [one])
    f3, b3 = work.packed_work(spec, [three])
    assert f3 == pytest.approx(3 * f1)
    assert b3 > b1                      # activations grow, weights do not
    fa, ba = work.paged_attention_work(spec, [three])
    per = 4.0 * spec.n_heads * spec.head_dim * spec.layers
    assert fa == pytest.approx(per * 60)
    prefill = client.Step(0, 1, 0, [], [(32, 32)])
    per_tok = 2.0 * spec.layers * sum(r * c for r, c in
                                      work.layer_matrices(spec))
    head = 2.0 * spec.vocab * spec.d_model
    attn = per * (32 * 32 + 32 * 33 / 2)
    assert work.model_flops(spec, [prefill]) == pytest.approx(
        32 * per_tok + head + attn)


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peaks) == 10.0
    assert work.least_time(100.0, 50.0, peaks) == 5.0
    assert work.share(1.0, 4.0) == 25.0
    assert work.share(1.0, 0.0) is None
