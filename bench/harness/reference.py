"""The plain reference: a float32 forward over each prompt and its
served tokens, with no cache, no kernels and nothing the program made.

It imports nothing of the program.  The weights are made again from the
seed (``harness.weights.make_leaves``) and decoded one layer at a time by
this module's own copy of the packed word layouts.  Quantized KV follows the
configuration's stated rule: each page of ``page_size`` positions gets a
``2**kv_bits``-entry codebook, fit by quantile-seeded 1-D k-means on the
first position written to it (all heads' features together), and every
position of the page keeps the codebook entry nearest to each feature.

``compute`` chooses the arithmetic: ``"f32"`` is float32 at the highest
matmul precision (the reference); ``"fp8"``, the control, rounds every
matmul operand to float8_e4m3fn with one scale per tensor (accumulating
in float32), one step below the bfloat16 operands the program's matmuls
take; ``"bf16"`` keeps every tensor in bfloat16 (matmuls accumulate in
float32, as the MXU does), a reading that does not separate (PERF.md).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W
from harness.spec import ModelSpec

HI = jax.lax.Precision.HIGHEST
# Query rows per attention block: bounds the [H, rows, S] score block.
Q_BLOCK = 256
KV_FIT_ITERS = 8        # k-means iterations of a page codebook fit
KV_FIT_TOL = 1e-4       # stop once the distortion improves less


def unpack_kd(words, rows: int, bits: int):
    """[W, n] words packed down the rows → [rows, n] int32 indices
    (lane l of word w holds row w*lanes + l at bit l*bits)."""
    lanes = 32 // bits
    shifts = jnp.arange(lanes, dtype=jnp.uint32) * bits
    idx = (words[:, None, :] >> shifts[None, :, None]) & ((1 << bits) - 1)
    return idx.reshape(-1, words.shape[-1])[:rows].astype(jnp.int32)


def unpack_rows(words, cols: int, bits: int):
    """[..., Wd] words packed along each row → [..., cols] indices."""
    lanes = 32 // bits
    shifts = jnp.arange(lanes, dtype=jnp.uint32) * bits
    idx = (words[..., None] >> shifts) & ((1 << bits) - 1)
    return idx.reshape(words.shape[:-1] + (-1,))[..., :cols].astype(
        jnp.int32)


def to_fp8(x):
    """x rounded to float8_e4m3fn under one scale that maps max|x| to the
    format's largest finite value, and back to float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


class Arith:
    """Matmuls and stored tensors in one precision."""

    def __init__(self, compute: str):
        if compute not in ("f32", "bf16", "fp8"):
            raise ValueError(f"unknown compute {compute!r}")
        self.store = jnp.bfloat16 if compute == "bf16" else jnp.float32
        self.operand = to_fp8 if compute == "fp8" else self.cast

    def cast(self, x):
        return x.astype(self.store)

    def mm(self, eq, a, b):
        return self.cast(jnp.einsum(eq, self.operand(a), self.operand(b),
                                    precision=HI,
                                    preferred_element_type=jnp.float32))


def rms_norm(x, scale, ar: Arith, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ar.cast(x32 * jax.lax.rsqrt(var + eps)
                   * (1.0 + scale.astype(jnp.float32)))


def rope(x, positions, theta):
    """Rotate the two halves of each head: x [n, S, H, hd]."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _assign(v, cb):
    """Nearest entry of an ascending codebook; ties go up."""
    mids = 0.5 * (cb[..., 1:] + cb[..., :-1])
    idx = jnp.zeros(v.shape, jnp.int32)
    for j in range(mids.shape[-1]):
        idx = idx + (v >= mids[..., j:j + 1]).astype(jnp.int32)
    return idx


def fit_codebook(v, k: int):
    """Quantile-seeded 1-D k-means of one row of values → [k] sorted."""
    v = v.astype(jnp.float32)
    c = jnp.sort(jnp.quantile(v, (jnp.arange(k) + 0.5) / k))

    def step(carry, _):
        c, prev, prev_dist, done = carry
        a = _assign(v, c)
        hit = a[:, None] == jnp.arange(k)
        sums = jnp.sum(jnp.where(hit, v[:, None], 0.0), axis=0)
        counts = jnp.sum(hit, axis=0)
        c_new = jnp.sort(jnp.where(counts > 0,
                                   sums / jnp.maximum(counts, 1), c))
        resid = v - jnp.take(c, a)
        dist = jnp.sum(resid * resid)
        changed = jnp.any(a != prev)
        plateau = (prev_dist - dist) <= KV_FIT_TOL * jnp.abs(dist)
        c = jnp.where(done, c, c_new)
        return (c, a, dist, done | ~changed | plateau), None

    init = (c, jnp.full(v.shape, -1, jnp.int32), jnp.float32(jnp.inf),
            jnp.asarray(False))
    (c, _, _, _), _ = jax.lax.scan(step, init, None, length=KV_FIT_ITERS)
    return c


def kv_quantize(x, bits: int, page: int):
    """x [n, S, KV, hd] → the values a codebook-quantized page holds."""
    n, s, kv, hd = x.shape
    rows = x.reshape(n, s // page, page, kv * hd).astype(jnp.float32)
    cb = jax.vmap(jax.vmap(lambda r: fit_codebook(r, 1 << bits)))(
        rows[:, :, 0])                                 # [n, P, K]
    idx = _assign(rows, cb[:, :, None, :])
    vals = jnp.take_along_axis(
        jnp.broadcast_to(cb[:, :, None, :], idx.shape[:3] + cb.shape[-1:]),
        idx, axis=-1)
    return vals.reshape(n, s, kv, hd)


def _attention(q, k, v, ar: Arith):
    """Causal attention; q [n, S, H, hd], k/v [n, S, KV, hd]."""
    n, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        logits = ar.mm("nqhd,nkhd->nhqk", qb, k).astype(jnp.float32)
        logits = logits * hd ** -0.5
        logits = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :,
                                                             None],
                           logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return ar.mm("nhqk,nkhd->nqhd", p, v)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))  # [nb, n, Q, H, hd]
    return out.transpose(1, 0, 2, 3, 4).reshape(n, s, h * hd)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, spec: ModelSpec, compute: str):
    ar = Arith(compute)
    n, s, _ = x.shape
    pos = jnp.arange(s)
    hd = spec.head_dim
    h = rms_norm(x, w["ln1_norm_scale"], ar)
    q = ar.mm("nsd,de->nse", h, w["wq"])
    k = ar.mm("nsd,de->nse", h, w["wk"])
    v = ar.mm("nsd,de->nse", h, w["wv"])
    if spec.qkv_bias:
        q = ar.cast(q + w["q_bias"])
        k = ar.cast(k + w["k_bias"])
        v = ar.cast(v + w["v_bias"])
    q = ar.cast(rope(q.reshape(n, s, spec.n_heads, hd), pos,
                     spec.rope_theta))
    k = ar.cast(rope(k.reshape(n, s, spec.n_kv, hd), pos, spec.rope_theta))
    v = v.reshape(n, s, spec.n_kv, hd)
    if spec.kv_bits:
        k = ar.cast(kv_quantize(k, spec.kv_bits, spec.page_size))
        v = ar.cast(kv_quantize(v, spec.kv_bits, spec.page_size))
    o = _attention(q, k, v, ar)
    x = ar.cast(x + ar.mm("nse,ed->nsd", o, w["wo"]))
    h = rms_norm(x, w["ln2_norm_scale"], ar)
    up = ar.mm("nsd,df->nsf", h, w["w_in"])
    gate = ar.mm("nsd,df->nsf", h, w["w_gate"])
    mid = ar.cast(jax.nn.silu(gate.astype(jnp.float32)) * up)
    return ar.cast(x + ar.mm("nsf,fd->nsd", mid, w["w_out"]))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_weights(made, g, spec: ModelSpec, compute: str):
    """Layer ``g``'s weights, decoded from its words and codebooks."""
    ar = Arith(compute)
    out = {}
    for leaf in W.leaves(spec):
        if not leaf.groups:
            continue
        val = jax.tree_util.tree_map(lambda x: x[g], made[leaf.path])
        if leaf.packed:
            words, cb = val
            val = ar.cast(cb)[unpack_kd(words, leaf.shape[0], spec.bits)]
        out[leaf.name] = ar.cast(val.astype(jnp.float32))
    return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(table, tokens, spec: ModelSpec, compute: str):
    ar = Arith(compute)
    words, cb = table
    x = ar.cast(cb)[unpack_rows(words[tokens], spec.d_model, spec.bits)]
    if spec.emb_scale is not None:
        x = x * spec.emb_scale
    return ar.cast(x)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(table, norm, x, rows, spec: ModelSpec, compute: str):
    """Tied-head logits (f32) at ``rows`` of the flattened [n*S] stream."""
    ar = Arith(compute)
    words, cb = table
    table = ar.cast(cb)[unpack_rows(words, spec.d_model, spec.bits)]
    h = rms_norm(x.reshape(-1, x.shape[-1])[rows], norm, ar)
    return ar.mm("md,vd->mv", h, table).astype(jnp.float32)


def logits_at(key_data, spec: ModelSpec, seqs: Sequence[Tuple], compute:
              str = "f32"):
    """Logits at every served position of ``seqs``.

    ``seqs``: (prompt, served tokens) pairs.  Position ``len(prompt) - 1
    + i`` predicts served token ``i``.  Sequences are padded to
    ``spec.max_seq`` (causal, so the padding changes no earlier
    position) and run together, one layer at a time.
    """
    s_pad = -(-spec.max_seq // Q_BLOCK) * Q_BLOCK
    n = len(seqs)
    tokens = np.zeros((n, s_pad), np.int32)
    rows = []
    for i, (prompt, served) in enumerate(seqs):
        seq = np.concatenate([prompt, served[:-1]])
        tokens[i, :seq.size] = seq
        p = len(prompt)
        rows.extend(i * s_pad + p - 1 + np.arange(len(served)))
    made = W.make_leaves(key_data, spec)
    table = made[("embed_tok",)]
    x = _embed(table, jnp.asarray(tokens), spec, compute)
    for g in range(spec.layers):
        w = _layer_weights(made, g, spec, compute)
        x = _layer(x, w, spec, compute)
        del w
    return np.asarray(_logits(table, made[("final_norm_scale",)], x,
                              jnp.asarray(rows, jnp.int32), spec, compute))


def served_gaps(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far below the reference's best each served token's logit
    lies (0 where the served token is the reference's argmax)."""
    got = ref_logits[np.arange(len(served)), served]
    return ref_logits.max(axis=-1) - got


def gap_stats(gaps: np.ndarray) -> dict:
    """The numbers a check may compare, over every served token: the
    widest gap, the mean gap, and the share of tokens that are not the
    reference's argmax."""
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "off_argmax_share": float(np.mean(gaps > 0))}


def compare(key_data, spec: ModelSpec, seqs: Sequence[Tuple],
            controls: Sequence[str] = ()) -> dict:
    """``{"program": stats, <control>: stats, ...}`` over the served
    tokens of ``seqs``.  A control is the reference computed in a lower
    precision and put in the program's place: at each position of the
    same prompts and served tokens, its token is the one it puts first,
    judged by the float32 reference as the served token is."""
    ref = logits_at(key_data, spec, seqs)
    served = np.concatenate([s for _, s in seqs])
    out = {"program": gap_stats(served_gaps(ref, served))}
    for c in controls:
        low = logits_at(key_data, spec, seqs, c)
        out[c] = gap_stats(served_gaps(ref, low.argmax(axis=-1)))
    return out
