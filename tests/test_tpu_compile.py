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
import dataclasses
import math
import os
import re

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
LAYERS = 2        # dense pools are stacked over a stack's layers


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
        pool = ((LAYERS, POOL, PAGE, H * HD), jnp.float32)
        _compile(chip, lambda q, k, v, ly, t, p, a: dispatch.paged_attention(
            q, k, v, ly, t, p, a, scale=HD ** -0.5, backend="pallas"),
            q, pool, pool, ((), jnp.int32), *meta)
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
        # lane-dense pools split their lanes by head, as the q heads split
        pool = arg((LAYERS, POOL, PAGE, H * HD), jnp.float32, None, None,
                   None, "model")
        fn = lambda q, k, v, ly, t, p, a: dispatch.paged_attention(  # noqa
            q, k, v, ly, t, p, a, scale=HD ** -0.5, backend="pallas",
            mesh=mesh4)
        args = (arg((SLOTS, 1, H, HD), jnp.float32, None, None, "model",
                    None), pool, pool, arg((), jnp.int32),
                arg((SLOTS, PAGES_PER_SLOT), jnp.int32),
                arg((SLOTS,), jnp.int32), arg((SLOTS,), jnp.bool_))
    with mesh4:
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the placement is the kernel's split: no weight or pool is gathered
    assert "all-gather" not in text


def _hlo_ops(text):
    """(op, element type, element count, line) of every instruction."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", line)
        if m:
            dt, dims, op = m.groups()
            out.append((op, dt, math.prod(int(x) for x in dims.split(",")
                                          if x), line))
    return out


def test_decode_program_updates_the_pool_in_place(chip, monkeypatch):
    """The engine's decode program at MiniCPM-2B's widths (d 2304, 36
    heads of 64, d_ff 5760, K=16), cut to 2 layers, over 8 slots and a
    pool of 481 pages of 16: the stacked pools ride the layer loop and
    are written in place.  No slice, update-slice or loop fusion makes a
    float32 array of one layer's pool or more, and the only copies that
    large are the two the call makes on entry, one per pool, because it
    does not own the caches it is given (they are not donated)."""
    from repro.configs import get_config
    from repro.core.compression import unflatten_paths
    from repro.engine.engine import _DECODE
    from repro.models.transformer import (LayerKind, init_paged_cache,
                                          uniform_stack)

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    g, k, slots, pages = 2, 16, 8, 480
    cfg = dataclasses.replace(get_config("minicpm-2b"),
                              stacks=uniform_stack(LayerKind("gqa", "dense"),
                                                   g))
    d, f = cfg.d_model, cfg.d_ff
    qd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv * cfg.head_dim

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    entries = {("final_norm_scale",): sds((d,), jnp.float32)}

    def packed(path, rows, cols, lead=(), order="kd"):
        layout = PackedLayout.make(rows, cols, k, order=order)
        head, name = path[:-1], path[-1]
        entries[head + (f"{name}_pidx",)] = sds(lead + layout.word_shape,
                                                jnp.uint32)
        entries[head + (f"{name}_cb",)] = sds(lead + (k,), jnp.float32)
        entries[head + (f"{name}_layout",)] = layout

    layer = ("stacks", 0, "pos0")
    packed(("embed_tok",), cfg.vocab, d, order="row")
    for name in ("ln1_norm_scale", "ln2_norm_scale"):
        entries[layer + (name,)] = sds((g, d), jnp.float32)
    for name, rows, cols in (("wq", d, qd), ("wk", d, kvd), ("wv", d, kvd),
                             ("wo", qd, d)):
        packed(layer + ("mixer", name), rows, cols, (g,))
    for name, rows, cols in (("w_in", d, f), ("w_gate", d, f),
                             ("w_out", f, d)):
        packed(layer + ("mlp", name), rows, cols, (g,))
    caches = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: init_paged_cache(cfg, slots, pages, PAGE)))
    step = (sds((slots, 96), jnp.int32), sds((slots, 1), jnp.int32),
            sds((slots,), jnp.int32), sds((slots,), jnp.bool_),
            sds((slots,), jnp.float32), sds((slots,), jnp.int32),
            sds((slots, 2), jnp.uint32), sds((slots,), jnp.bool_))
    text = _DECODE.lower(unflatten_paths(entries), cfg, caches,
                         *step).compile().as_text()

    assert "tpu_custom_call" in text
    one_layer = (pages + 1) * PAGE * kvd
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    moved = [(op, line.strip()[:160]) for op, dt, n, line in _hlo_ops(text)
             if dt == "f32" and n >= one_layer
             and (op in ("copy", "dynamic-slice", "dynamic-update-slice")
                  or (op == "fusion" and "kind=kLoop" in line))]
    stacked = f"f32[{g},{pages + 1},{PAGE},{kvd}]"
    copies = [line for op, line in moved
              if op == "copy" and line.split(" = ")[1].startswith(stacked)
              and line in entry]
    assert len(copies) == 2 and len(moved) == 2, moved
