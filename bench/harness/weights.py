"""Served weights made on the device from the seed.

Every packed leaf is random uint32 words in its packed shape (for K = 2
and K = 16 every bit pattern is a valid index) and a random sorted f32
codebook at the leaf's init scale, centred on zero as the codebooks of
zero-mean weights are (an off-centre codebook gives every matrix a
common component that grows through the layers into a logit offset far
larger than the logits' spread); the dense leaves (norm gains, biases)
are small random values in the served dtype.  Serving speed does not
depend on which indices the words hold, so no LC run or host packing is
needed; the configuration file lists this under ``assumed``.

Layer ``g`` of a leaf comes from ``fold_in(fold_in(key, crc32(path)),
g)``.  :func:`make_leaves` makes every leaf in one jitted call; the
reference calls it again from the seed, so it sees the same bits
without importing the program or taking an array it made.
:func:`serving_tree` is the only function here that imports the program:
it names the arrays as ``PackedModel.serving_params(packed=True)`` does
and attaches the program's ``PackedLayout``.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import ModelSpec

# Spread of the dense leaves (norm gains ride on 1 + scale).
DENSE_SCALE = 0.1


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: Tuple            # the program's tree path of the dense leaf
    shape: Tuple[int, ...]  # one group's shape
    groups: int            # stacked layers; 0 for a top-level leaf
    packed: str            # "kd" (matmul operand), "row" (gathered), ""
    scale: float

    @property
    def name(self) -> str:
        return self.path[-1]

    def word_shape(self, lanes: int) -> Tuple[int, int]:
        rows, cols = self.shape
        if self.packed == "kd":
            return (-(-rows // lanes), cols)
        return (rows, -(-cols // lanes))


def leaves(spec: ModelSpec) -> List[Leaf]:
    """Every served leaf of the model, in a fixed order."""
    d, f = spec.d_model, spec.d_ff
    qd, kvd = spec.n_heads * spec.head_dim, spec.n_kv * spec.head_dim
    g = spec.layers
    lay = ("stacks", 0, "pos0")
    # the scaled embedding enters the residual stream at the init scale
    emb = d ** -0.5 / (spec.emb_scale or 1.0)
    out = [Leaf(("embed_tok",), (spec.vocab, d), 0, "row", emb),
           Leaf(("final_norm_scale",), (d,), 0, "", DENSE_SCALE),
           Leaf(lay + ("ln1_norm_scale",), (d,), g, "", DENSE_SCALE),
           Leaf(lay + ("ln2_norm_scale",), (d,), g, "", DENSE_SCALE),
           Leaf(lay + ("mixer", "wq"), (d, qd), g, "kd", d ** -0.5),
           Leaf(lay + ("mixer", "wk"), (d, kvd), g, "kd", d ** -0.5),
           Leaf(lay + ("mixer", "wv"), (d, kvd), g, "kd", d ** -0.5),
           Leaf(lay + ("mixer", "wo"), (qd, d), g, "kd", qd ** -0.5),
           Leaf(lay + ("mlp", "w_in"), (d, f), g, "kd", d ** -0.5),
           Leaf(lay + ("mlp", "w_gate"), (d, f), g, "kd", d ** -0.5),
           Leaf(lay + ("mlp", "w_out"), (f, d), g, "kd", f ** -0.5)]
    if spec.qkv_bias:
        out += [Leaf(lay + ("mixer", "q_bias"), (qd,), g, "", DENSE_SCALE),
                Leaf(lay + ("mixer", "k_bias"), (kvd,), g, "", DENSE_SCALE),
                Leaf(lay + ("mixer", "v_bias"), (kvd,), g, "", DENSE_SCALE)]
    return out


def seed_key(seed: int) -> np.ndarray:
    """Raw threefry key data from a seed of any size."""
    return np.random.SeedSequence([int(seed), 0x57E1]).generate_state(
        2, np.uint32)


def _leaf_key(key_data, leaf: Leaf, group: int):
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    tag = zlib.crc32("/".join(map(str, leaf.path)).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.fold_in(key, tag), group)


def _one_group(key, leaf: Leaf, spec: ModelSpec):
    if not leaf.packed:
        x = jax.random.normal(key, leaf.shape, jnp.float32) * leaf.scale
        return x.astype(spec.dtype)
    words = jax.random.bits(key, leaf.word_shape(spec.lanes), jnp.uint32)
    cb = jnp.sort(jax.random.normal(jax.random.fold_in(key, 1), (spec.k,),
                                    jnp.float32))
    return words, (cb - jnp.mean(cb)) * leaf.scale


def _all_leaves(key_data, spec: ModelSpec) -> Dict[Tuple, object]:
    out = {}
    for leaf in leaves(spec):
        if leaf.groups:
            groups = jnp.arange(leaf.groups)
            keys = jax.vmap(lambda g: _leaf_key(key_data, leaf, g))(groups)
            out[leaf.path] = jax.vmap(
                lambda k: _one_group(k, leaf, spec))(keys)
        else:
            out[leaf.path] = _one_group(_leaf_key(key_data, leaf, 0), leaf,
                                        spec)
    return out


_make = jax.jit(_all_leaves, static_argnums=1)


def make_leaves(key_data, spec: ModelSpec) -> Dict[Tuple, object]:
    """path → ``(words, codebook)`` of a packed leaf or the dense array,
    every leaf at once in one jitted call on the default device; grouped
    leaves carry the layer axis first."""
    return _make(jnp.asarray(key_data, jnp.uint32), spec)


def serving_tree(key_data, spec: ModelSpec):
    """The program's ``serving_params(packed=True)`` tree for ``spec``."""
    from repro.core.compression import PackedLayout
    from repro.core.compression import unflatten_paths

    made = make_leaves(key_data, spec)
    entries = {}
    for leaf in leaves(spec):
        val = made[leaf.path]
        if not leaf.packed:
            entries[leaf.path] = val
            continue
        words, cb = val
        head, name = leaf.path[:-1], leaf.name
        rows, cols = leaf.shape
        entries[head + (f"{name}_pidx",)] = words
        entries[head + (f"{name}_cb",)] = cb
        entries[head + (f"{name}_layout",)] = PackedLayout.make(
            rows, cols, spec.k, dtype=spec.dtype, order=leaf.packed)
    return unflatten_paths(entries)
