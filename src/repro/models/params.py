"""Minimal parameter-tree system (no flax dependency).

A model describes its parameters as a pytree of ``ParamDef`` leaves; each leaf
carries the shape, dtype, *logical axis names* (for sharding) and an init
distribution. The same tree drives:

  * ``init_params``      — materialize (optionally directly onto a sharding)
  * ``abstract_params``  — ShapeDtypeStructs for ``jax.eval_shape``/dry-run
  * ``shardings_for``    — NamedSharding tree for pjit in_shardings
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.axes import logical_sharding


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    init: str = "normal"          # "normal" | "zeros" | "ones" | "small_normal" | "decay"
    scale: float = 1.0            # multiplies the distribution's natural scale
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (self.shape, self.logical_axes)

    def fan_in(self) -> int:
        # last dim is fan-out by convention; everything else contributes fan-in,
        # except leading stacked 'layers' dims.
        dims = [s for s, a in zip(self.shape[:-1], self.logical_axes[:-1]) if a != "layers"]
        return int(np.prod(dims)) if dims else 1


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _materialize(pd: ParamDef, key) -> jax.Array:
    dt = jnp.dtype(pd.dtype)
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, dt)
    if pd.init == "ones":
        return jnp.ones(pd.shape, dt)
    if pd.init == "decay":
        # rwkv/mamba decay-style init: negative, spread log-uniformly
        u = jax.random.uniform(key, pd.shape, jnp.float32, 1e-3, 1.0)
        return (jnp.log(u) * pd.scale).astype(dt)
    std = pd.scale * (pd.fan_in() ** -0.5)
    if pd.init == "small_normal":
        std = pd.scale * 0.02
    return (jax.random.normal(key, pd.shape, jnp.float32) * std).astype(dt)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def init_params(defs, key):
    """Materialize a ParamDef tree. Keys are derived per-leaf from the tree
    path, so adding parameters never reshuffles existing ones. The path is
    hashed with CRC-32, not ``hash()``, whose value for a ``str`` changes
    from one process to the next: the same key gives the same weights in
    every process."""
    def one(path, pd):
        if not is_def(pd):
            return pd
        path_hash = zlib.crc32(_path_str(path).encode()) % (2**31)
        leaf_key = jax.random.fold_in(key, path_hash)
        return _materialize(pd, leaf_key)

    return jax.tree_util.tree_map_with_path(one, defs, is_leaf=is_def)


def abstract_params(defs):
    """ShapeDtypeStruct tree (no allocation) for lowering."""
    return jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, jnp.dtype(pd.dtype)),
        defs, is_leaf=is_def)


def shardings_for(defs, mesh, rules=None):
    return jax.tree.map(
        lambda pd: logical_sharding(pd.shape, pd.logical_axes, mesh, rules),
        defs, is_leaf=is_def)


def param_count(defs) -> int:
    leaves = [l for l in jax.tree.leaves(defs, is_leaf=is_def) if is_def(l)]
    return sum(int(np.prod(l.shape)) for l in leaves)


def param_bytes(defs) -> int:
    leaves = [l for l in jax.tree.leaves(defs, is_leaf=is_def) if is_def(l)]
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize for l in leaves)
