"""Compile the served Pallas kernels for a described TPU v5e, at the
shapes the engine dispatches when it serves qwen1.5-0.5b (d_model 1024,
d_ff 2816, 16 heads of 64, vocab 151936; 4 slots, 16-token pages, 64-token
prefill blocks, 288-token slots).

No chip is needed: libtpu compiles for a topology that is described, not
attached, so Mosaic's refusals (unsupported ops, (8, 128) block tiling,
VMEM) surface here.  Nothing runs, so these tests say nothing about
results or times.  The topology is described inside a module fixture —
never at import — so that under pytest-xdist only the worker given this
file loads libtpu, and every worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import kvquant
from repro.core.compression import PackedLayout, bits_per_index
from repro.kernels import dispatch

D, F, V, H, HD = 1024, 2816, 151936, 16, 64
SLOTS, PAGE, PAGES_PER_SLOT, CHUNK = 4, 16, 18, 64
POOL = SLOTS * PAGES_PER_SLOT + 1


@pytest.fixture(scope="module")
def topology():
    """A described v5e:2x2, with the persistent compile cache
    off (an entry written for a chip that is not attached cannot be read
    back here, and warns on the next compile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def mesh4(topology):
    """The (data=1, model=4) serving mesh over the four described chips."""
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 4), ("data", "model"), devices=topology.devices)


def _compile(chip, fn, *shapes):
    """Lower ``fn`` at ``shapes`` ((shape, dtype) pairs) for the chip and
    compile it; the program must hold a Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _lanes(k):
    return 32 // bits_per_index(k)


@pytest.mark.parametrize("k,kd,n", [(2, D, F), (16, F, D)])
def test_packed_matmul_decode_blocks(chip, k, kd, n):
    _compile(chip, lambda x, p, c: dispatch.packed_codebook_matmul(
        x, p, c, backend="pallas"),
        ((SLOTS, kd), jnp.float32), ((kd // _lanes(k), n), jnp.uint32),
        ((k,), jnp.float32))


def test_packed_matmul_prefill_blocks(chip):
    _compile(chip, lambda x, p, c: dispatch.packed_codebook_matmul(
        x, p, c, backend="pallas"),
        ((CHUNK, D), jnp.float32), ((D // 8, F), jnp.uint32),
        ((16,), jnp.float32))


def test_tied_head_row_order(chip):
    layout = PackedLayout.make(V, D, 16, order="row")
    _compile(chip, lambda x, p, c: dispatch.packed_quantized_matmul_t(
        x, p, c, layout=layout, backend="pallas"),
        ((SLOTS, D), jnp.float32), (layout.word_shape, jnp.uint32),
        ((16,), jnp.float32))


def test_quantized_gather(chip):
    layout = PackedLayout.make(V, D, 16, order="row")
    _compile(chip, lambda t, p, c: dispatch.quantized_gather(
        t, p, c, layout=layout, backend="pallas"),
        ((CHUNK,), jnp.int32), (layout.word_shape, jnp.uint32),
        ((16,), jnp.float32))


@pytest.mark.parametrize("kv_bits", [0, 4])
def test_paged_attention(chip, kv_bits):
    q = ((SLOTS, 1, H, HD), jnp.float32)
    meta = [((SLOTS, PAGES_PER_SLOT), jnp.int32), ((SLOTS,), jnp.int32),
            ((SLOTS,), jnp.bool_)]
    if kv_bits == 0:
        pool = ((POOL, PAGE, H, HD), jnp.float32)
        _compile(chip, lambda q, k, v, t, p, a: dispatch.paged_attention(
            q, k, v, t, p, a, scale=HD ** -0.5, backend="pallas"),
            q, pool, pool, *meta)
        return
    words = ((POOL, PAGE, H, kvquant.words_per(HD, kv_bits)), jnp.uint32)
    cbs = ((POOL, 1, kvquant.kv_entries(kv_bits)), jnp.float32)
    _compile(chip, lambda q, kw, vw, kc, vc, t, p, a:
             dispatch.paged_attention_quant(
                 q, kw, vw, kc, vc, t, p, a, bits=kv_bits, head_dim=HD,
                 scale=HD ** -0.5, backend="pallas"),
             q, words, words, cbs, cbs, *meta)


@pytest.mark.parametrize("kv_bits", [0, 4])
def test_blockwise_prefill(chip, kv_bits):
    s = PAGES_PER_SLOT * PAGE
    q = ((1, CHUNK, H, HD), jnp.float32)
    pos = [((CHUNK,), jnp.int32), ((s,), jnp.int32)]
    if kv_bits == 0:
        view = ((1, s, H, HD), jnp.float32)
        _compile(chip, lambda q, k, v, qp, kp:
                 dispatch.blockwise_prefill_attention(
                     q, k, v, qp, kp, scale=HD ** -0.5, backend="pallas"),
                 q, view, view, *pos)
        return
    words = ((1, s, H, kvquant.words_per(HD, kv_bits)), jnp.uint32)
    cbs = ((1, PAGES_PER_SLOT, 1, kvquant.kv_entries(kv_bits)),
           jnp.float32)
    _compile(chip, lambda q, kw, vw, kc, vc, qp, kp:
             dispatch.blockwise_prefill_attention_quant(
                 q, kw, vw, kc, vc, qp, kp, page_size=PAGE, bits=kv_bits,
                 head_dim=HD, scale=HD ** -0.5, backend="pallas"),
             q, words, words, cbs, cbs, *pos)


@pytest.mark.parametrize("route", ["col", "row", "head", "gather", "paged"])
def test_tensor_parallel_routes(mesh4, route):
    """The kernel routes under the four-chip serving mesh, with operands
    placed as ``dist.sharding`` places them: each compiles inside its
    ``shard_map`` at the local shapes (N or Kd / 4, vocab / 4, heads / 4)."""
    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh4, P(*spec)))
    cb = arg((16,), jnp.float32)
    if route in ("col", "row"):
        kd, n = (D, F) if route == "col" else (F, D)
        layout = PackedLayout.make(kd, n, 16)
        spec = (None, "model") if route == "col" else ("model", None)
        fn = lambda x, p, c: dispatch.packed_quantized_matmul(  # noqa: E731
            x, p, c, layout=layout, backend="pallas", mesh=mesh4,
            split=route)
        args = (arg((SLOTS, 1, kd), jnp.float32),
                arg(layout.word_shape, jnp.uint32, *spec), cb)
    elif route in ("head", "gather"):
        layout = PackedLayout.make(V, D, 16, order="row")
        words = arg(layout.word_shape, jnp.uint32, "model", None)
        if route == "head":
            fn = lambda x, p, c: dispatch.packed_quantized_matmul_t(  # noqa
                x, p, c, layout=layout, backend="pallas", mesh=mesh4)
            args = (arg((SLOTS, 1, D), jnp.float32), words, cb)
        else:
            fn = lambda t, p, c: dispatch.quantized_gather(  # noqa: E731
                t, p, c, layout=layout, backend="pallas", mesh=mesh4)
            args = (arg((1, CHUNK), jnp.int32), words, cb)
    else:
        heads = (None, None, "model", None)
        pool = arg((POOL, PAGE, H, HD), jnp.float32, *heads)
        fn = lambda q, k, v, t, p, a: dispatch.paged_attention(  # noqa
            q, k, v, t, p, a, scale=HD ** -0.5, backend="pallas",
            mesh=mesh4)
        args = (arg((SLOTS, 1, H, HD), jnp.float32, *heads), pool, pool,
                arg((SLOTS, PAGES_PER_SLOT), jnp.int32),
                arg((SLOTS,), jnp.int32), arg((SLOTS,), jnp.bool_))
    with mesh4:
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the placement is the kernel's split: no weight or pool is gathered
    assert "all-gather" not in text
