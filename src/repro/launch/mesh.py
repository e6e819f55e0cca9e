"""Mesh builders.  FUNCTIONS ONLY — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any import).

Every mesh of the program comes from :func:`make_mesh`, with ``Auto``
axis types: JAX ≥ 0.9 makes ``jax.make_mesh`` axes ``Explicit`` by
default, and explicit axes reject the ``with_sharding_constraint`` specs
``models.sharding_ctx`` injects and the implicit output shardings of the
gathers and contractions in the model code.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — "pod" is
pure data parallelism across the cross-pod (DCN-class) links, which is
where the int8 gradient-compression collective (repro/dist/compress.py)
applies.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

AXES = ("pod", "data", "model")


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler, constrained by ``sharding_ctx``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def mesh_from_spec(spec: Optional[str] = None) -> jax.sharding.Mesh:
    """The launchers' ``--mesh``: ``"AxB"`` → (data, model), ``"AxBxC"`` →
    (pod, data, model), one number → (model,).  ``None`` → (data=1,
    model=n) over the ``n`` devices that exist."""
    if spec is None:
        return make_mesh((1, len(jax.devices())), ("data", "model"))
    dims = tuple(int(x) for x in spec.split("x"))
    return make_mesh(dims, AXES[-len(dims):])


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    return make_mesh(shape, AXES[-len(shape):])


def make_debug_mesh(n_data: int = 2, n_model: int = 2,
                    pods: int = 0) -> jax.sharding.Mesh:
    """Small mesh for subprocess tests (XLA_FLAGS device count permitting)."""
    if pods:
        return make_mesh((pods, n_data, n_model), AXES)
    return make_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh):
    """The batch-sharding axes present in this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
