"""Optimal scalar quantization operators (paper §4.2, Theorems A.1-A.3).

These solve the C step ``min_Θ ||w - Δ(Θ)||²`` in closed form for fixed
codebooks, with or without a learned global scale.  Every operator is pure
jnp, jit/vmap/grad-safe (piecewise-constant ⇒ zero gradient, which is what
the LC algorithm wants: the C step is *not* differentiated through).

Conventions
-----------
* ``sgn(0) = +1`` (paper eq. 12).
* Ties at Voronoi boundaries round toward the *larger* codebook index
  (paper eq. 11: ``(c_{k-1}+c_k)/2 <= t < (c_k+c_{k+1})/2``).
* All operators take/return arrays of any shape; scale-solving operators
  reduce over *all* elements (callers flatten per quantization group, or
  vmap over a leading group axis).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def sgn(t: Array) -> Array:
    """Sign with sgn(0) = +1 (paper eq. 12)."""
    return jnp.where(t >= 0, 1.0, -1.0).astype(t.dtype)


# ---------------------------------------------------------------------------
# Fixed codebook, no scale (paper eq. 11 particular cases)
# ---------------------------------------------------------------------------

def binarize(t: Array) -> Array:
    """q(t) for codebook {-1,+1}: q = sgn(t)."""
    return sgn(t)


def ternarize(t: Array) -> Array:
    """q(t) for codebook {-1,0,+1}: q = sgn(t)·1[|t| ≥ 1/2]."""
    return sgn(t) * (jnp.abs(t) >= 0.5).astype(t.dtype)


def pow2_quantize(t: Array, C: int) -> Array:
    """q(t) for codebook {0, ±1, ±2^-1, ..., ±2^-C} (Theorem A.1).

    α(t) = 0              if f > C+1
           1              if f ≤ 0
           2^-C           if f ∈ (C, C+1]
           2^-⌊f+log2(3/2)⌋ otherwise,      f = -log2|t|.
    """
    if C < 0:
        raise ValueError(f"pow2 codebook needs C >= 0, got {C}")
    at = jnp.abs(t)
    # Guard log2(0): where at==0 we force the f > C+1 branch.
    safe = jnp.where(at > 0, at, 1.0)
    f = -jnp.log2(safe)
    f = jnp.where(at > 0, f, jnp.inf)
    mid_exp = jnp.floor(f + jnp.log2(1.5))
    alpha = jnp.where(
        f > C + 1,
        0.0,
        jnp.where(
            f <= 0,
            1.0,
            jnp.where(f > C, 2.0 ** (-float(C)), 2.0 ** (-mid_exp)),
        ),
    )
    return (alpha * sgn(t)).astype(t.dtype)


def fixed_codebook_quantize(t: Array, codebook: Array) -> Array:
    """q(t) for an arbitrary fixed scalar codebook (paper eq. 11).

    ``codebook`` is a 1-D array; it need not be sorted (we sort internally).
    Returns the quantized values (same shape as ``t``).
    """
    c = jnp.sort(codebook)
    return c[fixed_codebook_assign(t, c)]


# Codebooks up to this size run the k-means blocks (assignment, cluster
# sums, lookup) as K-way fused compare/select passes, all jitted.  On
# v5e chips the direct-compression C step of qwen1.5-0.5b did not finish
# within 1500 s with the binary search, scatter-add and gather forms; it
# took 12.5 s at K=16 this way, and 227 s for a 4-layer cut when only
# the cluster sums were masked.  Larger codebooks keep the O(log K) and
# scatter forms, whose cost does not grow with K.
MASKED_MAX_K = 16


@jax.jit
def fixed_codebook_assign(t: Array, sorted_codebook: Array) -> Array:
    """Voronoi assignment indices into an ascending-sorted codebook.

    Ties at midpoints go to the larger index (paper eq. 11).
    """
    mids = 0.5 * (sorted_codebook[1:] + sorted_codebook[:-1])
    # side='right': t == midpoint → larger index, matching eq. (11).
    if sorted_codebook.shape[0] > MASKED_MAX_K:
        return jnp.searchsorted(mids, t, side="right").astype(jnp.int32)
    # The same count of midpoints ≤ t as K-1 fused compares: one pass
    # over t with no [N, K-1] temporary.
    idx = jnp.zeros(jnp.shape(t), jnp.int32)
    for j in range(mids.shape[0]):
        idx = idx + (t >= mids[j]).astype(jnp.int32)
    return idx


# ---------------------------------------------------------------------------
# Fixed codebook with learned global scale (Theorems A.2, A.3)
# ---------------------------------------------------------------------------

def binarize_scale(w: Array) -> Tuple[Array, Array]:
    """Codebook {-a,+a}, optimal a (Theorem A.2).

    Returns (q, a) with a* = mean(|w|), q = a·sgn(w).
    """
    a = jnp.mean(jnp.abs(w))
    return a * sgn(w), a


def ternarize_scale(w: Array) -> Tuple[Array, Array]:
    """Codebook {-a,0,+a}, exact optimal a (Theorem A.3).

    j* = argmax_j (1/√j) Σ_{i≤j} |w|_(i)  over |w| sorted descending,
    a* = (1/j*) Σ_{i≤j*} |w|_(i),   q_i = sgn(w_i)·a·1[|w_i| ≥ a/2].

    Exact (sort-based) — O(P log P).  For the distributed variant see
    :mod:`repro.dist.cstep` (histogram-CDF reformulation).
    """
    flat = jnp.abs(w).ravel()
    s = jnp.sort(flat)[::-1]                       # descending magnitudes
    csum = jnp.cumsum(s)
    j = jnp.arange(1, flat.size + 1, dtype=csum.dtype)
    obj = csum / jnp.sqrt(j)
    jstar = jnp.argmax(obj)
    a = csum[jstar] / (jstar + 1).astype(csum.dtype)
    q = sgn(w) * a * (jnp.abs(w) >= 0.5 * a).astype(w.dtype)
    return q.astype(w.dtype), a


def fixed_scale_fit(
    w: Array,
    codebook: Array,
    iters: int = 20,
) -> Tuple[Array, Array, Array]:
    """General fixed codebook with adaptive scale (paper eq. 13).

    Alternates assignment and the closed-form scale step
    ``a = Σ z_ik w_i c_k / Σ z_ik c_k²`` until ``iters`` iterations.
    Returns (q, a, assignments).  Used for codebooks without a closed form
    (e.g. scaled powers-of-two); binarize/ternarize have exact solutions
    above and are preferred.
    """
    flat = w.ravel()
    c = jnp.sort(codebook.astype(flat.dtype))
    csq = c * c

    def body(a, _):
        assign = fixed_codebook_assign(flat, a * c)
        ck = c[assign]
        num = jnp.sum(flat * ck)
        den = jnp.sum(csq[assign])
        a_new = jnp.where(den > 0, num / den, a)
        return a_new, None

    a0 = jnp.maximum(jnp.mean(jnp.abs(flat)), jnp.finfo(flat.dtype).tiny)
    a, _ = jax.lax.scan(body, a0, None, length=iters)
    assign = fixed_codebook_assign(flat, a * c)
    q = (a * c[assign]).reshape(w.shape)
    return q, a, assign.reshape(w.shape)


# ---------------------------------------------------------------------------
# Distortion helper (used by tests / benchmarks)
# ---------------------------------------------------------------------------

def distortion(w: Array, q: Array) -> Array:
    """Squared error ||w - q||² — the C-step objective."""
    d = (w - q).ravel()
    return jnp.dot(d, d)


# Named registry of parameter-free operators (bench/test sweeps).
FIXED_OPS = {
    "binary": binarize,
    "ternary": ternarize,
    "pow2_c4": functools.partial(pow2_quantize, C=4),
    "pow2_c7": functools.partial(pow2_quantize, C=7),
}
