"""Logical-axis sharding rules (MaxText-style, minimal).

Every tensor dimension in the framework is tagged with a *logical* axis name;
``logical_spec`` maps logical names -> mesh axes through ``LOGICAL_RULES``,
dropping mesh axes that are absent from the current mesh and demoting any
mapping whose dimension size is not divisible by the mapped mesh extent
(e.g. kv_heads=4 on a 16-way 'model' axis -> replicated).

This single rule table is the *unified memory layout* of the TPU adaptation:
one parameter sharding serves the GEMM (prefill/train) path and the GEMV
(decode) path, so no resharding/duplication ever happens between phases —
the IANUS unified-memory property (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> candidate mesh axes (applied in order, all that fit)
LOGICAL_RULES: dict = {
    # data-parallel axes
    "batch": ("pod", "data"),
    # sequence: replicated by default; the SP hillclimb remaps it (see perf log)
    "seq": (),
    # decode KV-cache sequence dim: falls back to 'model' when kv_heads could
    # not claim it (GQA with kv_heads < model extent) — sequence-sharded cache
    "kv_seq": ("model",),
    # tensor-parallel axes
    "heads": ("model",),
    "kv_heads": ("model",),
    "d_ff": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "d_inner": ("model",),
    "rwkv_heads": ("model",),
    # ZeRO-3 weight dim: resident shards over 'data' (+ 'pod' when present),
    # all-gathered at use (GSPMD) or computed in place (EP shard_map)
    "fsdp": ("data", "pod"),
    # replicated axes
    "d_model": (),
    "head_dim": (),
    "d_state": (),
    "conv": (),
    "capacity": (),
    "layers": (),     # the scan-stacked layer dimension
    "stack": (),      # fused-QKV stack dim and similar
    None: (),
}


# ---------------------------------------------------------------------------
# rule profiles: the parallelism layout is itself a PAS-style routing decision
# (DESIGN.md: "route the workload to the engine/layout whose roofline fits").
#   tp  — default: TP over 'model', DP over ('pod','data')  [paper-faithful]
#   dp  — pure data parallelism over ALL axes: small dense models whose
#         TP collectives dominate (the llama3.2-1b train hillclimb, §Perf)
# ---------------------------------------------------------------------------
import contextvars

_DP_RULES = dict(LOGICAL_RULES)
_DP_RULES.update({
    "batch": ("pod", "data", "model"),
    "heads": (), "kv_heads": (), "d_ff": (), "vocab": (),
    "experts": (), "d_inner": (), "rwkv_heads": (), "fsdp": (),
    "kv_seq": (),
})

PROFILES = {"tp": LOGICAL_RULES, "dp": _DP_RULES}

_active_profile: contextvars.ContextVar = contextvars.ContextVar(
    "sharding_profile", default="tp")


def set_profile(name: str):
    assert name in PROFILES, name
    return _active_profile.set(name)


def active_rules() -> dict:
    return PROFILES[_active_profile.get()]


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    """Cached view of the active mesh."""
    mesh: Mesh

    @property
    def axis_sizes(self) -> dict:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def extent(self, axes: Sequence[str]) -> int:
        s = 1
        for a in axes:
            s *= self.axis_sizes.get(a, 1)
        return s


def _resolve_dim(dim_size: int, logical: Optional[str], info: MeshInfo,
                 used: set, rules: Optional[dict] = None):
    """Mesh axes for one dimension, respecting presence, divisibility, and
    axes already claimed by earlier dims of the same tensor."""
    rules = rules or active_rules()
    cand = rules.get(logical, ())
    present = [a for a in cand
               if a in info.axis_sizes and info.axis_sizes[a] > 1 and a not in used]
    # use the longest prefix of candidate axes whose product divides dim_size
    chosen: Tuple[str, ...] = ()
    ext = 1
    for a in present:
        if dim_size % (ext * info.axis_sizes[a]) == 0:
            chosen = chosen + (a,)
            ext *= info.axis_sizes[a]
        else:
            break
    used.update(chosen)
    if not chosen:
        return None
    return chosen if len(chosen) > 1 else chosen[0]


def logical_spec(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
                 mesh: Mesh, rules: Optional[dict] = None) -> P:
    """PartitionSpec for `shape` whose dims carry `logical_axes` names.

    Dims are resolved left-to-right, except that kv_seq goes last; a mesh
    axis claimed by an earlier dim is unavailable to later dims (e.g. a
    decode KV cache (batch, kv_seq, kv_heads, hd): batch claims 'data';
    kv_heads claims 'model' when divisible, otherwise kv_seq claims 'model'
    — the GQA-aware fallback, whatever the order of the two dims)."""
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    info = MeshInfo(mesh)
    used: set = set()
    order = sorted(range(len(shape)),
                   key=lambda i: logical_axes[i] == "kv_seq")
    spec = [None] * len(shape)
    for i in order:
        spec[i] = _resolve_dim(shape[i], logical_axes[i], info, used, rules)
    return P(*spec)


def logical_sharding(shape, logical_axes, mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(shape, logical_axes, mesh, rules))


def constrain(x, logical_axes, mesh=None, rules=None):
    """with_sharding_constraint by logical names (no-op without a mesh)."""
    if mesh is None:
        mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = logical_spec(x.shape, logical_axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh() -> Optional[Mesh]:
    env = jax._src.mesh.thread_resources.env  # the `with mesh:` context
    m = env.physical_mesh
    if m is not None and not m.empty:
        return m
    return None


def param_sharding_tree(abstract_params, mesh, rules=None):
    """Map a pytree of ShapeDtypeStruct-with-logical-axes (see models.param)
    to a pytree of NamedSharding."""
    def one(leaf):
        axes = getattr(leaf, "logical_axes", None)
        if axes is None:
            return NamedSharding(mesh, P())
        return logical_sharding(leaf.shape, axes, mesh, rules)
    return jax.tree.map(one, abstract_params,
                        is_leaf=lambda l: hasattr(l, "logical_axes"))
