"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the 'pod' axis
carries only data parallelism (gradient all-reduce crosses DCN, everything
else stays intra-pod) — the standard multi-pod recipe.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, **kwargs):
    """``jax.make_mesh`` with Auto axes. Its default is Explicit, while the
    sharding rules (``repro.sharding``) place arrays through
    ``with_sharding_constraint``, which needs Auto axes."""
    kwargs.setdefault("axis_types", (AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 0):
    """A small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data <= 0:
        data = max(1, n // model)
    return make_mesh((data, model), ("data", "model"))
