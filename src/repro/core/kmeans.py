"""Exact 1-D k-means for adaptive-codebook quantization (paper §4.1).

The C step with an adaptive codebook is the quadratic-distortion problem
(eq. 9), solved by k-means.  For scalar weights each iteration is exact in
O(P log K): sort the K centroids once, then a weight belongs to centroid k
iff it falls between the midpoints of (c_{k-1},c_k) and (c_k,c_{k+1})
— a ``searchsorted`` over K-1 midpoints (paper §4.1, eq. 11 geometry).

Supports:
* weighted points (used by the histogram-compressed distributed C step);
* warm start (LC C steps re-use the previous codebook: paper Fig. 10 shows
  ~1 iteration suffices after the first);
* k-means++ initialization for the first C step (paper §3.3);
* an optional mesh ``axis_name`` — inside ``shard_map`` the per-centroid
  statistics are psum'd, giving the exact *global* k-means update while the
  weight shards never leave their chips (2·K floats of traffic/iteration).
* vmapped per-group fits via ``jax.vmap`` (stacked-layer codebooks).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.quant_ops import MASKED_MAX_K, fixed_codebook_assign

Array = jax.Array


class KMeansResult(NamedTuple):
    codebook: Array      # [K] ascending
    assignments: Array   # same shape as input points, int32
    distortion: Array    # scalar Σ n_i (w_i - c_{κ(i)})²
    iters_run: Array     # scalar int32 — iterations until assignment fixpoint


def kmeans_plus_plus_init(key: Array, w: Array, k: int) -> Array:
    """k-means++ seeding (Arthur & Vassilvitskii 2007) on scalar points."""
    flat = w.ravel()
    p = flat.size
    k0, key = jax.random.split(key)
    first = flat[jax.random.randint(k0, (), 0, p)]
    cents = jnp.full((k,), first, flat.dtype)
    d2 = (flat - first) ** 2

    def body(i, carry):
        cents, d2, key = carry
        key, sub = jax.random.split(key)
        # D² sampling; degenerate (all-zero) distances fall back to uniform.
        total = jnp.sum(d2)
        probs = jnp.where(total > 0, d2 / total, jnp.full_like(d2, 1.0 / p))
        idx = jax.random.choice(sub, p, p=probs)
        c_new = flat[idx]
        cents = cents.at[i].set(c_new)
        d2 = jnp.minimum(d2, (flat - c_new) ** 2)
        return cents, d2, key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, d2, key))
    return jnp.sort(cents)


def quantile_init(w: Array, k: int) -> Array:
    """Deterministic quantile seeding — the distributed-friendly default.

    Exact on a single device; under sharding callers pass a (histogram-)
    approximated quantile vector instead.
    """
    qs = (jnp.arange(k, dtype=jnp.float32) + 0.5) / k
    return jnp.quantile(w.ravel().astype(jnp.float32), qs).astype(w.dtype)


@functools.partial(jax.jit, static_argnames="k")
def cluster_sums(values: Array, assign: Array, k: int) -> Array:
    """[k] per-cluster sums of ``values`` over ids ``assign`` in [0, k).

    Up to ``MASKED_MAX_K`` clusters: a masked reduction XLA fuses into
    one pass — a scatter-add with k ≪ N colliding ids serializes on a
    TPU (jit keeps the [N, K] mask fused when this is called eagerly).
    Larger k keeps the scatter-add.
    """
    if k > MASKED_MAX_K:
        return jax.ops.segment_sum(values.ravel(), assign.ravel(),
                                   num_segments=k)
    hit = assign[..., None] == jnp.arange(k, dtype=assign.dtype)
    return jnp.sum(jnp.where(hit, values[..., None], 0),
                   axis=tuple(range(values.ndim)))


@jax.jit
def codebook_lookup(c: Array, assign: Array) -> Array:
    """``c[assign]``; up to ``MASKED_MAX_K`` entries as a fused masked
    sum (exact: one non-zero term per weight) instead of a per-weight
    gather."""
    if c.shape[0] > MASKED_MAX_K:
        return c[assign]
    hit = assign[..., None] == jnp.arange(c.shape[0], dtype=assign.dtype)
    return jnp.sum(jnp.where(hit, c, 0), axis=-1)


@functools.partial(jax.jit, static_argnames=("iters", "axis_name", "tol"))
def kmeans_fit(
    w: Array,
    init_codebook: Array,
    iters: int = 30,
    point_weights: Optional[Array] = None,
    axis_name: Optional[Union[str, Sequence[str]]] = None,
    tol: float = 1e-4,
) -> KMeansResult:
    """Run ≤ ``iters`` exact 1-D k-means iterations from ``init_codebook``.

    Iterations after convergence are no-ops (pure-jnp loops must have static
    trip counts); ``iters_run`` reports when convergence was reached — the
    paper's Fig. 10 warm-start claim is measured with it.  Convergence is
    either the assignment fixpoint or a distortion plateau: relative
    improvement ≤ ``tol`` per iteration.  The plateau stop is what makes
    warm starts cheap — near the optimum, boundary points can keep flipping
    between adjacent cells for many iterations while the distortion is
    already flat.

    Empty clusters keep their previous centroid (can re-acquire points later).
    """
    flat = w.ravel()
    nw = jnp.ones_like(flat) if point_weights is None else point_weights.ravel()
    k = init_codebook.shape[0]

    def psum(x):
        return jax.lax.psum(x, axis_name) if axis_name is not None else x

    def step(carry, _):
        c, prev_assign, prev_dist, done, n_run = carry
        assign = fixed_codebook_assign(flat, c)
        sums = psum(cluster_sums(flat * nw, assign, k))
        counts = psum(cluster_sums(nw, assign, k))
        c_new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), c)
        c_new = jnp.sort(c_new)
        # Convergence must be GLOBAL: a shard whose local assignments are
        # already stable must keep iterating with the others, else the
        # replicated codebooks diverge across shards.  (The distortion is
        # psum'd, so the plateau criterion is global too.)
        changed = jnp.any(assign != prev_assign).astype(jnp.float32)
        changed = psum(changed) > 0
        # f32 accumulation: the carry slot is f32, and bf16 would both
        # break the scan carry-type match and swamp the plateau test.
        resid = (flat - codebook_lookup(c, assign)).astype(jnp.float32)
        dist = psum(jnp.sum(nw.astype(jnp.float32) * resid * resid))
        plateau = (prev_dist - dist) <= tol * jnp.abs(dist)
        # Freeze once converged so iters_run is the true fixpoint index.
        c_out = jnp.where(done, c, c_new)
        n_run = n_run + jnp.where(done, 0, 1)
        done = done | ~changed | plateau
        return (c_out, assign, dist, done, n_run), None

    c0 = jnp.sort(init_codebook.astype(flat.dtype))
    init = (c0, jnp.full(flat.shape, -1, jnp.int32),
            jnp.asarray(jnp.inf, jnp.float32), jnp.asarray(False),
            jnp.asarray(0, jnp.int32))
    (c, _, _, _, n_run), _ = jax.lax.scan(step, init, None, length=iters)

    assign = fixed_codebook_assign(flat, c)
    resid = (flat - codebook_lookup(c, assign)).astype(jnp.float32)
    dist = psum(jnp.sum(nw.astype(jnp.float32) * resid * resid))
    return KMeansResult(c, assign.reshape(w.shape), dist, n_run)


def kmeans_quantize(
    w: Array,
    codebook: Array,
) -> Array:
    """Δ(Θ): decompress — map each weight to its assigned codebook entry."""
    c = jnp.sort(codebook)
    return c[fixed_codebook_assign(w, c)].astype(w.dtype)


# Per-group (stacked-layer) variants: codebooks [G, K], weights [G, ...].
kmeans_fit_grouped = jax.vmap(
    lambda w, c, iters: kmeans_fit(w, c, iters=iters),
    in_axes=(0, 0, None),
)


def quantile_init_grouped(w: Array, k: int) -> Array:
    """[G, ...] weights → [G, K] quantile codebooks."""
    return jax.vmap(lambda x: quantile_init(x, k))(w)
