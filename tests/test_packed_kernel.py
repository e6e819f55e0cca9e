"""Bit-packed serve-path correctness: pack_indices_2d / pack_rows →
in-kernel unpack is bit-exact, and the three packed kernels
(packed_codebook_matmul, packed_codebook_matmul_t, quantized_gather —
interpret mode) match the dense-gather oracle for bits ∈ {1, 2, 4, 8},
non-pow2 K, and ragged M/Kd/N tails.  Deterministic sweeps always run;
hypothesis fuzzing skips when hypothesis is not installed (``pip install
-r requirements-dev.txt``), like test_schemes_property.py.  The Mosaic
compiles of the same kernels live in test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:        # dev-only dep: fuzzing skips, sweeps still run
    given = None

from repro.core import compression as C
from repro.kernels import dispatch, ops, ref
from repro.kernels.unpack import dequant_tile

# K values spanning bits ∈ {1, 2, 3, 4, 8}, pow2 and non-pow2.
K_SWEEP = [2, 3, 4, 5, 16, 200, 256]


def _rand_case(k, kd, n, seed=0):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, k, size=(kd, n))
    pidx = jnp.asarray(C.pack_indices_2d(idx, k))
    cb = jnp.asarray(rng.randn(k), jnp.float32)
    return idx, pidx, cb


@pytest.mark.parametrize("k", K_SWEEP)
@pytest.mark.parametrize("kd,n", [(32, 16), (300, 77), (1024, 128)])
def test_pack2d_unpack2d_roundtrip(k, kd, n):
    idx, pidx, _ = _rand_case(k, kd, n, seed=kd + k)
    out = np.asarray(C.unpack_indices_2d(pidx, kd, k))
    np.testing.assert_array_equal(out, idx)


@pytest.mark.parametrize("k", K_SWEEP)
def test_in_kernel_unpack_bit_exact(k):
    """pack → in-kernel unpack is bit-exact vs unpack_indices_2d: with
    x = I and cb = [0..K), the kernel output IS the unpacked index tile
    (small ints are exact in f32)."""
    kd, n = 96, 40
    idx, pidx, _ = _rand_case(k, kd, n, seed=k)
    cb = jnp.arange(k, dtype=jnp.float32)
    bits = C.bits_per_index(k)
    lanes = 32 // bits
    y = ops.packed_codebook_matmul(jnp.eye(kd, dtype=jnp.float32), pidx, cb,
                                   bm=32, bn=32, bk=4 * lanes)
    np.testing.assert_array_equal(np.asarray(y).astype(np.int64), idx)
    up = np.asarray(C.unpack_indices_2d(pidx, kd, k))
    np.testing.assert_array_equal(up, idx)


@pytest.mark.parametrize("k", K_SWEEP)
@pytest.mark.parametrize("m,kd,n", [(8, 32, 16), (100, 300, 77),
                                    (1, 2048, 1), (33, 130, 257)])
def test_packed_matmul_matches_ref(m, kd, n, k):
    """interpret-mode packed kernel == ref.codebook_matmul_ref ∘ unpack
    to fp32 tolerance, including ragged M/Kd/N tails."""
    idx, pidx, cb = _rand_case(k, kd, n, seed=m + kd + n + k)
    x = jnp.asarray(np.random.RandomState(m + n).randn(m, kd), jnp.float32)
    bits = C.bits_per_index(k)
    lanes = 32 // bits
    y1 = ops.packed_codebook_matmul(x, pidx, cb, bm=32, bn=64, bk=4 * lanes)
    y2 = ref.codebook_matmul_ref(x, jnp.asarray(idx), cb)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=3e-5, atol=3e-4)


@pytest.mark.parametrize("k", [2, 16, 256])
def test_dequant_tile_matches_take(k):
    """The compare+select dequant every kernel shares == cb[idx],
    bitwise (it only selects codebook entries)."""
    rng = np.random.RandomState(k)
    idx = jnp.asarray(rng.randint(0, k, size=(24, 40)), jnp.int32)
    cb = jnp.asarray(rng.randn(k), jnp.float32)
    out = dequant_tile(idx, cb[None, :])
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(cb)[np.asarray(idx)])


def test_uint8_kernel_matches_ref():
    """The uint8-index kernel over a K=256 codebook == dequant-then-dot."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(24, 128), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 256, size=(128, 48)), jnp.uint8)
    cb = jnp.asarray(rng.randn(256), jnp.float32)
    y = ops.codebook_matmul(x, idx, cb, bm=32, bn=32, bk=64)
    y_ref = ref.codebook_matmul_ref(x, idx, cb)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k", K_SWEEP)
@pytest.mark.parametrize("kd,n", [(32, 16), (300, 77)])
def test_pack_rows_unpack_rows_roundtrip(k, kd, n):
    idx, _, _ = _rand_case(k, kd, n, seed=kd + k)
    words = C.pack_rows(idx, k)
    assert words.shape == (kd, -(-n // (32 // C.bits_per_index(k))))
    out = np.asarray(C.unpack_rows(jnp.asarray(words), n, k))
    np.testing.assert_array_equal(out, idx)


@pytest.mark.parametrize("k", K_SWEEP)
@pytest.mark.parametrize("order", ["kd", "row"])
@pytest.mark.parametrize("m,v,d", [(8, 32, 16), (5, 77, 50), (1, 257, 33)])
def test_packed_matmul_t_matches_ref(m, v, d, k, order):
    """interpret-mode transposed kernel == dequant-then-dot oracle to fp32
    tolerance, both word orders, including ragged M/V/D tails."""
    idx, _, cb = _rand_case(k, v, d, seed=m + v + d + k)
    x = jnp.asarray(np.random.RandomState(m + d).randn(m, d), jnp.float32)
    lanes = 32 // C.bits_per_index(k)
    if order == "kd":
        pidx = jnp.asarray(C.pack_indices_2d(idx, k))
        bn, bk = 2 * lanes, 16
    else:
        pidx = jnp.asarray(C.pack_rows(idx, k))
        bn, bk = 16, 2 * lanes
    y1 = ops.packed_codebook_matmul_t(x, pidx, cb, v, order=order, bm=8,
                                      bn=bn, bk=bk)
    want = np.asarray(x) @ np.asarray(cb)[idx].T
    np.testing.assert_allclose(np.asarray(y1), want, rtol=3e-5, atol=3e-4)
    y2 = ref.packed_codebook_matmul_t_ref(x, pidx, cb, v, order=order)
    np.testing.assert_allclose(np.asarray(y2), want, rtol=3e-5, atol=3e-4)


@pytest.mark.parametrize("k", K_SWEEP)
def test_gather_kernel_matches_dense_rows_bitwise(k):
    """interpret-mode gather kernel == dense-table row gather, bitwise
    (a pure gather — no arithmetic), ragged D included."""
    v, d = 50, 13
    idx, _, cb = _rand_case(k, v, d, seed=k)
    pidx = jnp.asarray(C.pack_rows(idx, k))
    toks = jnp.asarray(np.random.RandomState(k).randint(0, v, size=(9,)),
                       jnp.int32)
    dense = np.asarray(cb)[idx]
    g = ops.quantized_gather(toks, pidx, cb, d)
    np.testing.assert_array_equal(np.asarray(g), dense[np.asarray(toks)])


def test_dispatch_packed_route_and_layout_validation():
    idx, pidx, cb = _rand_case(16, 128, 64, seed=11)
    x = jnp.asarray(np.random.RandomState(1).randn(8, 128), jnp.float32)
    layout = C.PackedLayout.make(128, 64, 16)
    for backend in ("ref", "pallas_interpret"):
        y = dispatch.packed_codebook_matmul(x, pidx, cb, layout=layout,
                                            backend=backend)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.codebook_matmul_ref(
                x, jnp.asarray(idx), cb)), rtol=3e-5, atol=3e-4)
    with pytest.raises(ValueError, match="layout"):
        dispatch.packed_codebook_matmul(
            x, pidx, cb, layout=C.PackedLayout.make(64, 64, 16),
            backend="ref")


def test_packed_block_sizes_lane_aligned(monkeypatch):
    monkeypatch.delenv("REPRO_PACKED_BLOCKS", raising=False)
    for m, kd, n in [(1, 2048, 512), (64, 1024, 256), (512, 4096, 1024),
                     (33, 100, 100)]:
        for bits in (1, 2, 3, 4, 8):
            bm, bn, bk = dispatch.packed_block_sizes(m, kd, n, bits)
            assert bk % (32 // bits) == 0, (m, kd, n, bits, bk)
            assert bm > 0 and bn > 0
            # transposed route: the lane-packed axis depends on the order
            bm, bn, bk = dispatch.packed_block_sizes_t(m, kd, n, bits, "kd")
            assert bn % (32 // bits) == 0, (m, kd, n, bits, bn)
            bm, bn, bk = dispatch.packed_block_sizes_t(m, kd, n, bits, "row")
            assert bk % (32 // bits) == 0, (m, kd, n, bits, bk)
    monkeypatch.setenv("REPRO_PACKED_BLOCKS", "16,64,128")
    assert dispatch.packed_block_sizes(7, 99, 13, 4) == (16, 64, 128)


def test_serving_params_packed_no_uint8():
    """serving_params(packed=True) must not materialize any index array
    wider than the packed uint32 words, and apply_mlp over the packed
    layout must match the uint8 layout and the dense decode."""
    from repro.models import layers as L

    rng = np.random.RandomState(0)
    k = 16
    d, f = 48, 96
    key = jax.random.PRNGKey(0)
    params = {"mlp": L.init_mlp(key, d, f, "silu", True)}

    from repro.core import CompressionPlan
    plan = CompressionPlan.parse(f"adaptive:{k}")
    qspec = plan.build_qspec(params)
    state = plan.init(key, params, qspec)
    packed = plan.pack(params, state, qspec)

    sp = packed.serving_params(packed=True)
    up = packed.serving_params(packed=False)
    mlp_p, mlp_u = sp["mlp"], up["mlp"]
    for name in ("w_in", "w_gate", "w_out"):
        assert f"{name}_pidx" in mlp_p and f"{name}_idx" not in mlp_p
        assert mlp_p[f"{name}_pidx"].dtype == jnp.uint32
        layout = mlp_p[f"{name}_layout"]
        assert isinstance(layout, C.PackedLayout)
        assert mlp_p[f"{name}_pidx"].shape == (layout.words, layout.n)
        # HBM index bytes per weight == bits/8 (kd here divides lanes).
        nbytes = mlp_p[f"{name}_pidx"].size * 4
        assert nbytes * 8 == layout.bits * layout.kd * layout.n

    x = jnp.asarray(rng.randn(5, d), jnp.float32)
    y_p = L.apply_mlp(mlp_p, x, "silu")
    y_u = L.apply_mlp(mlp_u, x, "silu")
    dense = packed.decode()["mlp"]
    y_d = L.apply_mlp(dense, x, "silu")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_u),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_d),
                               rtol=1e-4, atol=1e-4)
    # decode_params collapses the packed layout to the same dense leaves
    dec = dispatch.decode_params(sp)
    np.testing.assert_allclose(np.asarray(dec["mlp"]["w_in"]),
                               np.asarray(dense["w_in"]), rtol=0, atol=0)


def test_grouped_packed_serving_under_scan():
    """Grouped (stacked-layer) packed leaves: the static PackedLayout node
    rides through jax.lax.scan and each group's slice decodes exactly."""
    from repro.kernels.dispatch import decode_packed_leaf

    rng = np.random.RandomState(4)
    g, kd, n, k = 3, 64, 32, 4
    idx = rng.randint(0, k, size=(g, kd, n))
    words = jnp.asarray(np.stack([C.pack_indices_2d(i, k) for i in idx]))
    cb = jnp.asarray(rng.randn(g, k), jnp.float32)
    layout = C.PackedLayout.make(kd, n, k)

    dense = decode_packed_leaf(words, cb, layout)
    ref_dense = np.stack([np.asarray(cb)[i][idx[i]] for i in range(g)])
    np.testing.assert_allclose(np.asarray(dense), ref_dense, rtol=0, atol=0)

    xs = {"pidx": words, "cb": cb, "layout": layout}
    x = jnp.asarray(rng.randn(2, kd), jnp.float32)

    def body(carry, p):
        y = dispatch.packed_quantized_matmul(x, p["pidx"], p["cb"],
                                             layout=p["layout"])
        return carry + jnp.sum(y), None

    total, _ = jax.lax.scan(body, 0.0, xs)
    expect = sum(float(jnp.sum(x @ jnp.asarray(ref_dense[i])))
                 for i in range(g))
    np.testing.assert_allclose(float(total), expect, rtol=1e-5)


if given is not None:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 256), st.integers(1, 500), st.integers(1, 64),
           st.integers(0, 10 ** 6))
    def test_packed_matmul_fuzz(k, kd, n, seed):
        rng = np.random.RandomState(seed)
        idx = rng.randint(0, k, size=(kd, n))
        pidx = jnp.asarray(C.pack_indices_2d(idx, k))
        cb = jnp.asarray(rng.randn(k), jnp.float32)
        x = jnp.asarray(rng.randn(4, kd), jnp.float32)
        lanes = 32 // C.bits_per_index(k)
        y1 = ops.packed_codebook_matmul(x, pidx, cb, bm=8, bn=32,
                                        bk=2 * lanes)
        y2 = ref.codebook_matmul_ref(x, jnp.asarray(idx), cb)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=3e-5, atol=3e-4)
else:
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev.txt)")
    def test_packed_matmul_fuzz():
        pass
