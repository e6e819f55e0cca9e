"""Activation-sharding policy context (GSPMD constraint injection).

Without explicit constraints, XLA's SPMD partitioner free-runs on the
layer loop and (for large per-chip batches) picks the comm-minimal plan:
all-gather the layer weights and **replicate the matmuls across the model
axis** — 16× wasted FLOPs on a 16-way TP mesh (measured on qwen train_4k;
see EXPERIMENTS.md §Perf iteration 0).  Production frameworks pin
activation shardings at block boundaries; this module is that mechanism.

Model code calls ``constrain(x, "batch", None, "heads", None)`` with
*logical* axis names; the active policy maps them to mesh axes and applies
``jax.lax.with_sharding_constraint`` — or no-ops when no policy is set
(single-device tests) or when a dim is not divisible by its mesh axes.

Logical axes: batch, seq, heads, kv_heads, ffn, experts, width, vocab,
embed (all map to "model" except batch → (pod, data)).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE: dict = {"policy": None}

_MODEL_AXES = ("heads", "kv_heads", "ffn", "experts", "width", "vocab",
               "embed", "ssm_heads")


class Policy:
    """mode: "tp" (megatron TP + EP), "dp" (pure data parallel — model
    axis joins the batch axes), or "none"."""

    def __init__(self, mesh: Mesh, mode: str = "tp",
                 seq_axis: Optional[str] = None):
        self.mesh = mesh
        self.mode = mode
        self.daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        self.seq_axis = seq_axis
        sizes = dict(mesh.shape)
        self.model_size = sizes.get("model", 1)
        self.batch_size_div = 1
        for a in self.daxes:
            self.batch_size_div *= sizes[a]

    def axes_for(self, logical: Optional[str], dim: int):
        if logical is None:
            return None, 1
        if logical == "batch":
            if self.mode == "dp":
                axes = self.daxes + ("model",)
                return axes, self.batch_size_div * self.model_size
            if self.mode == "tp2d":
                return None, 1          # decode: tiny activations, replicate
            return self.daxes, self.batch_size_div
        if logical == "seq" and self.seq_axis:
            return self.seq_axis, self.mesh.shape[self.seq_axis]
        if logical in _MODEL_AXES:
            if self.mode == "tp":
                return "model", self.model_size
            if self.mode == "tp2d":
                # weight-stationary decode: channel dims over the full mesh;
                # head dims unconstrained — pinning them forces GSPMD to
                # re-gather the 256-way projection weights to the head
                # grouping (measured 85 MB/layer on nemotron decode);
                # resharding the [B,1,D] activations instead costs ~nothing
                if logical in ("heads", "kv_heads", "ssm_heads"):
                    return None, 1
                axes = ("model",) + self.daxes
                return axes, self.model_size * self.batch_size_div
        return None, 1


@contextlib.contextmanager
def activation_policy(policy: Optional[Policy]):
    prev = _ACTIVE["policy"]
    _ACTIVE["policy"] = policy
    try:
        yield
    finally:
        _ACTIVE["policy"] = prev


def set_policy(policy: Optional[Policy]):
    _ACTIVE["policy"] = policy


def active_mesh() -> Optional[Mesh]:
    """The active policy's mesh (None without a policy or in "none"
    mode).  Model call sites pass it to ``kernels.dispatch``, which maps
    each Pallas route over it with that kernel's partition specs."""
    pol: Optional[Policy] = _ACTIVE["policy"]
    return None if pol is None or pol.mode == "none" else pol.mesh


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply the active policy's sharding to ``x`` (no-op without one)."""
    pol: Optional[Policy] = _ACTIVE["policy"]
    if pol is None or pol.mode == "none":
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    parts = []
    for dim, name in zip(x.shape, logical):
        axes, size = pol.axes_for(name, dim)
        if axes is None or size <= 1 or dim % size != 0:
            parts.append(None)
        else:
            parts.append(axes)
    if all(p is None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pol.mesh, P(*parts)))
