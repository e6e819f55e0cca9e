"""Quantization scheme registry — pluggable C-step solvers.

A *scheme* bundles the decompression form Δ(Θ) with its optimal C-step
solver Π(w) (paper §4).  Every scheme exposes the same tiny functional
interface so the LC driver, the baselines (DC/iDC), and the serving path
are scheme-agnostic:

    state = scheme.init(key, w)            # Θ-side state (codebook/scale)
    q, state = scheme.c_step(w, state, first=bool)   # solve eq. (8)
    scheme.bits_per_weight                  # storage accounting

``w`` here is one *quantization group* (a flat view of one layer's
multiplicative weights, or a [G, ...] stack — see ``grouped``).  Biases &
co. are excluded at the qspec level (paper §5: only multiplicative weights
are quantized).

Schemes register themselves under a spec name with :func:`register_scheme`;
``make_scheme("adaptive:4")`` resolves through that registry (structured
``name[:arg]`` parse + per-factory validation), so downstream packages can
add schemes without touching this module.  :class:`repro.core.plan
.CompressionPlan` is the preferred entry point and carries a Scheme built
here.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant_ops
from repro.core.kmeans import (
    codebook_lookup,
    kmeans_fit,
    kmeans_plus_plus_init,
    kmeans_quantize,
    quantile_init,
)

Array = jax.Array
SchemeState = Dict[str, Array]


@dataclasses.dataclass(frozen=True)
class Scheme:
    """Base class; concrete schemes override the four methods below."""

    name: str = "base"

    # -- storage accounting ------------------------------------------------
    @property
    def bits_per_weight(self) -> int:
        raise NotImplementedError

    @property
    def codebook_entries(self) -> int:
        """Float entries stored alongside the indices (K, or 1 for a scale)."""
        raise NotImplementedError

    @property
    def index_entries(self) -> int:
        """Size of the assignment index space (the K of pack_indices)."""
        raise NotImplementedError

    @property
    def spec(self) -> str:
        """Canonical ``make_scheme`` spec string (artifact manifests)."""
        return self.name

    # -- algorithm ----------------------------------------------------------
    def init(self, key: Array, w: Array) -> SchemeState:
        raise NotImplementedError

    def c_step(
        self, w: Array, state: SchemeState, first: bool = False
    ) -> Tuple[Array, SchemeState]:
        """Solve Π(w): return (quantized weights, new Θ state)."""
        raise NotImplementedError

    def assignments(self, w: Array, state: SchemeState) -> Array:
        """Codebook indices for packing/serving."""
        raise NotImplementedError

    def decode(self, assign: Array, state: SchemeState) -> Array:
        """Δ(Θ): indices → quantized weights."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class AdaptiveScheme(Scheme):
    """Adaptive codebook of size K — C step is exact 1-D k-means (§4.1)."""

    k: int = 4
    iters_first: int = 50
    iters_warm: int = 5
    init_method: str = "kmeans++"   # or "quantile" (deterministic/distributed)
    name: str = "adaptive"

    @property
    def bits_per_weight(self) -> int:
        return max(1, math.ceil(math.log2(self.k)))

    @property
    def codebook_entries(self) -> int:
        return self.k

    @property
    def index_entries(self) -> int:
        return self.k

    @property
    def spec(self) -> str:
        return f"{self.name}:{self.k}"

    def init(self, key: Array, w: Array) -> SchemeState:
        if self.init_method == "kmeans++":
            cb = kmeans_plus_plus_init(key, w, self.k)
        else:
            cb = quantile_init(w, self.k)
        # "kmeans_iters" present from init so the state pytree structure is
        # stable across init/c_step (required for jitted LC loops).
        return {"codebook": cb, "kmeans_iters": jnp.asarray(0, jnp.int32)}

    def c_step(self, w, state, first=False):
        iters = self.iters_first if first else self.iters_warm
        res = kmeans_fit(w, state["codebook"], iters=iters)
        q = codebook_lookup(res.codebook, res.assignments)
        return q.astype(w.dtype), {"codebook": res.codebook,
                                   "kmeans_iters": res.iters_run}

    def assignments(self, w, state):
        return quant_ops.fixed_codebook_assign(w, state["codebook"])

    def decode(self, assign, state):
        return state["codebook"][assign]


@dataclasses.dataclass(frozen=True)
class AdaptiveZeroScheme(AdaptiveScheme):
    """Adaptive codebook with one centroid PINNED at 0 — quantization +
    pruning jointly (the paper's §4.2 footnote 2: "we can also achieve
    pruning together with quantization by having one centroid be fixed to
    zero").

    C step: k-means over the K-1 free centroids with the zero entry
    participating in assignments (weights nearest 0 are pruned); the
    centroid update simply skips index of the zero entry (we re-pin it
    after each iteration — equivalent to a constrained centroid step).
    """

    name: str = "adaptive_zero"

    def init(self, key: Array, w: Array) -> SchemeState:
        st = super().init(key, w)
        cb = st["codebook"]
        zi = jnp.argmin(jnp.abs(cb))
        st["codebook"] = jnp.sort(cb.at[zi].set(0.0))
        return st

    def c_step(self, w, state, first=False):
        iters = self.iters_first if first else self.iters_warm
        cb = state["codebook"]

        def body(c, _):
            assign = quant_ops.fixed_codebook_assign(w.ravel(), c)
            sums = jax.ops.segment_sum(w.ravel(), assign, num_segments=self.k)
            counts = jax.ops.segment_sum(jnp.ones(w.size), assign,
                                         num_segments=self.k)
            c_new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), c)
            zi = jnp.argmin(jnp.abs(c_new))
            return jnp.sort(c_new.at[zi].set(0.0)), None

        cb, _ = jax.lax.scan(body, cb, None, length=iters)
        assign = quant_ops.fixed_codebook_assign(w.ravel(), cb)
        q = cb[assign].reshape(w.shape)
        return q.astype(w.dtype), {"codebook": cb,
                                   "kmeans_iters": jnp.asarray(iters, jnp.int32)}

    def sparsity(self, w: Array, state: SchemeState) -> Array:
        """Fraction of weights pruned (assigned to the zero centroid)."""
        q = state["codebook"][self.assignments(w, state)]
        return jnp.mean((q == 0.0).astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class FixedScheme(Scheme):
    """Parameter-free fixed codebook: binary / ternary / pow2(C) (§4.2)."""

    kind: str = "binary"          # binary | ternary | pow2
    pow2_c: int = 4
    name: str = "fixed"

    def _codebook(self, dtype) -> Array:
        if self.kind == "binary":
            return jnp.asarray([-1.0, 1.0], dtype)
        if self.kind == "ternary":
            return jnp.asarray([-1.0, 0.0, 1.0], dtype)
        if self.kind == "pow2":
            mags = [0.0] + [2.0 ** (-c) for c in range(self.pow2_c + 1)]
            vals = sorted({s * m for m in mags for s in (-1.0, 1.0)})
            return jnp.asarray(vals, dtype)
        raise ValueError(self.kind)

    @property
    def _k(self) -> int:
        return {"binary": 2, "ternary": 3}.get(self.kind, 2 * (self.pow2_c + 1) + 1)

    @property
    def bits_per_weight(self) -> int:
        return max(1, math.ceil(math.log2(self._k)))

    @property
    def codebook_entries(self) -> int:
        return 0  # fixed values: nothing stored

    @property
    def index_entries(self) -> int:
        return self._k

    @property
    def spec(self) -> str:
        return f"pow2:{self.pow2_c}" if self.kind == "pow2" else self.kind

    def init(self, key, w):
        return {"codebook": self._codebook(jnp.float32)}

    def c_step(self, w, state, first=False):
        if self.kind == "binary":
            return quant_ops.binarize(w), state
        if self.kind == "ternary":
            return quant_ops.ternarize(w), state
        return quant_ops.pow2_quantize(w, self.pow2_c), state

    def assignments(self, w, state):
        return quant_ops.fixed_codebook_assign(w, state["codebook"].astype(w.dtype))

    def decode(self, assign, state):
        return state["codebook"][assign]


@dataclasses.dataclass(frozen=True)
class ScaledFixedScheme(Scheme):
    """Fixed codebook with a learned global scale a (§4.2.1, Thms A.2/A.3)."""

    kind: str = "binary_scale"    # binary_scale | ternary_scale
    name: str = "scaled_fixed"

    @property
    def _k(self) -> int:
        return 2 if self.kind == "binary_scale" else 3

    @property
    def bits_per_weight(self) -> int:
        return 1 if self.kind == "binary_scale" else 2

    @property
    def codebook_entries(self) -> int:
        return 1  # the scale

    @property
    def index_entries(self) -> int:
        return self._k

    @property
    def spec(self) -> str:
        return self.kind

    def init(self, key, w):
        return {"scale": jnp.mean(jnp.abs(w))}

    def c_step(self, w, state, first=False):
        if self.kind == "binary_scale":
            q, a = quant_ops.binarize_scale(w)
        else:
            q, a = quant_ops.ternarize_scale(w)
        return q, {"scale": a}

    def assignments(self, w, state):
        a = state["scale"]
        base = jnp.asarray([-1.0, 1.0] if self.kind == "binary_scale"
                           else [-1.0, 0.0, 1.0], w.dtype)
        return quant_ops.fixed_codebook_assign(w, a * base)

    def decode(self, assign, state):
        a = state["scale"]
        base = jnp.asarray([-1.0, 1.0] if self.kind == "binary_scale"
                           else [-1.0, 0.0, 1.0], jnp.float32)
        return a * base[assign]


def as_scheme(obj: Any) -> Scheme:
    """Normalize a plan-or-scheme argument: anything carrying a Scheme
    under ``.scheme`` (a CompressionPlan) unwraps; a Scheme passes
    through.  Every plan-aware entry point calls this once at its
    boundary."""
    scheme = getattr(obj, "scheme", obj)
    if not isinstance(scheme, Scheme):
        raise TypeError(f"expected a Scheme or CompressionPlan, got {obj!r}")
    return scheme


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

SchemeFactory = Callable[..., Scheme]
_REGISTRY: Dict[str, SchemeFactory] = {}


def register_scheme(name: str, *aliases: str):
    """Decorator registering ``factory(arg: Optional[str], **kw) -> Scheme``
    under ``name`` (+ aliases).  ``arg`` is the text after the first ``:``
    in a spec like ``adaptive:4`` (None when absent); the factory owns its
    validation and raises ``ValueError`` on a malformed arg."""
    def deco(factory: SchemeFactory) -> SchemeFactory:
        for n in (name,) + aliases:
            if n in _REGISTRY:
                raise ValueError(f"scheme {n!r} registered twice")
            _REGISTRY[n] = factory
        return factory
    return deco


def registered_schemes() -> List[str]:
    return sorted(_REGISTRY)


def parse_spec(spec: str) -> Tuple[str, Optional[str]]:
    """``"adaptive:4"`` → ``("adaptive", "4")``; ``"binary"`` → ``("binary",
    None)``.  Validates the name against the registry."""
    name, _, arg = spec.partition(":")
    name = name.strip()
    if name not in _REGISTRY:
        raise ValueError(f"unknown scheme spec {spec!r}; registered: "
                         f"{registered_schemes()}")
    return name, (arg.strip() or None) if arg else None


def _int_arg(name: str, arg: Optional[str], default: int, lo: int) -> int:
    if arg is None:
        return default
    try:
        val = int(arg)
    except ValueError as e:
        raise ValueError(f"scheme {name!r}: arg {arg!r} is not an int") from e
    if val < lo:
        raise ValueError(f"scheme {name!r}: arg must be ≥ {lo}, got {val}")
    return val


@register_scheme("adaptive")
def _make_adaptive(arg: Optional[str] = None, **kw: Any) -> Scheme:
    k = _int_arg("adaptive", arg, kw.pop("k", 4), lo=2)
    return AdaptiveScheme(k=k, **kw)


@register_scheme("adaptive_zero")
def _make_adaptive_zero(arg: Optional[str] = None, **kw: Any) -> Scheme:
    k = _int_arg("adaptive_zero", arg, kw.pop("k", 4), lo=2)
    return AdaptiveZeroScheme(k=k, **kw)


@register_scheme("pow2")
def _make_pow2(arg: Optional[str] = None, **kw: Any) -> Scheme:
    c = _int_arg("pow2", arg, kw.pop("pow2_c", 4), lo=0)
    return FixedScheme(kind="pow2", pow2_c=c, **kw)


def _register_parameter_free(kind: str, cls) -> None:
    @register_scheme(kind)
    def factory(arg: Optional[str] = None, **kw: Any) -> Scheme:
        if arg is not None:
            raise ValueError(f"scheme {kind!r} takes no arg, got {arg!r}")
        kw.setdefault("kind", kind)
        return cls(**kw)


for _kind in ("binary", "ternary"):
    _register_parameter_free(_kind, FixedScheme)
for _kind in ("binary_scale", "ternary_scale"):
    _register_parameter_free(_kind, ScaledFixedScheme)


def make_scheme(spec: str, **kw: Any) -> Scheme:
    """Resolve a spec string (``adaptive:4``, ``binary``, ``ternary_scale``,
    ``pow2:4``) through the registry — the CLI / config / shim entry point.
    Prefer ``CompressionPlan.parse`` in new code."""
    name, arg = parse_spec(spec)
    return _REGISTRY[name](arg, **kw)
