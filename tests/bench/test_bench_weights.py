"""The on-device weight maker builds the tree the program serves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from harness import weights
from harness.spec import ModelSpec


@pytest.mark.parametrize("k", [2, 16])
def test_same_tree_as_serving_params(k):
    from repro.core import CompressionPlan
    from repro.models.transformer import init_params

    cfg = bench_tiny.program_config()
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    plan = CompressionPlan.parse(f"adaptive:{k}")
    qspec = plan.build_qspec(params)
    state = plan.init(jax.random.PRNGKey(1), params, qspec)
    want = plan.pack(params, state, qspec).serving_params(packed=True)
    spec = ModelSpec.from_config(bench_tiny.config(k=k))
    got = weights.serving_tree(weights.seed_key(2**40 + 3), spec)
    (want_leaves, want_def) = jax.tree_util.tree_flatten_with_path(want)
    (got_leaves, got_def) = jax.tree_util.tree_flatten_with_path(got)
    assert got_def == want_def          # names and static PackedLayouts
    for (pw, w), (pg, g) in zip(want_leaves, got_leaves):
        assert pw == pg
        assert (g.shape, g.dtype) == (w.shape, w.dtype), pw


def test_reference_makes_the_same_bits():
    """The reference calls make_leaves again from the seed; it must see
    the arrays the served tree holds, bit for bit."""
    spec = ModelSpec.from_config(bench_tiny.config())
    key = weights.seed_key(5)
    tree = weights.serving_tree(key, spec)
    again = weights.make_leaves(key, spec)
    for leaf in weights.leaves(spec):
        node = tree
        for p in leaf.path[:-1]:
            node = node[p]
        served = ((node[f"{leaf.name}_pidx"], node[f"{leaf.name}_cb"])
                  if leaf.packed else node[leaf.name])
        for a, b in zip(jax.tree_util.tree_leaves(served),
                        jax.tree_util.tree_leaves(again[leaf.path])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seed_changes_the_weights():
    spec = ModelSpec.from_config(bench_tiny.config())
    path = ("stacks", 0, "pos0", "mixer", "wq")
    a = weights.make_leaves(weights.seed_key(1), spec)[path][0]
    b = weights.make_leaves(weights.seed_key(2), spec)[path][0]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
