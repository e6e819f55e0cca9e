"""Requests of a traffic mix, made from its file and the seed.

The sizes are the quantiles of the mix's length distributions at evenly
spaced levels, paired and ordered once in a fixed way: every seed sends
the same requests in the same order.  The seed chooses only the prompts'
token ids, so the work of a window does not depend on the seed and the
spread between runs is the system's, not the draw's.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

# The pairing of prompt and output lengths, and their order, are fixed
# for every seed.
_PAIRING_SEED = 20240406
_ORDER_SEED = 20240407


def _levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _normal_ppf(u: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error below 1.2e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    u = np.asarray(u, np.float64)
    out = np.empty_like(u)
    lo, hi = u < 0.02425, u > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(u[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                          + 1)
    q = np.sqrt(-2 * np.log(1 - u[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                           + 1)
    q = u[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    return out


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist``, clipped to
    [min, max] and rounded up to a multiple of ``multiple``."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = dist["median"] * np.exp(dist["sigma"] * _normal_ppf(_levels(n)))
    m = dist.get("multiple", 1)
    x = np.ceil(np.clip(x, lo, hi) / m) * m
    return np.clip(x, lo, hi).astype(np.int64)


@dataclasses.dataclass
class Item:
    prompt: np.ndarray    # int32 token ids
    max_new: int


def items(mix: Dict, seed: int, vocab: int) -> List[Item]:
    """The requests of ``mix``, ``per_client`` for each of its clients,
    in the mix's fixed order, with token ids drawn from ``seed``."""
    n = mix["clients"] * mix["per_client"]
    pl = lengths(mix["prompt"], n)
    ol = lengths(mix["output"], n)
    ol = ol[np.random.default_rng(_PAIRING_SEED).permutation(n)]
    order = np.random.default_rng(_ORDER_SEED).permutation(n)
    pl, ol = pl[order], ol[order]
    rng = np.random.default_rng([int(seed), 0x7A4F])
    return [Item(rng.integers(0, vocab, size=int(pl[i])).astype(np.int32),
                 int(ol[i])) for i in range(n)]
