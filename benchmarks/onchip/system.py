"""The system under test, and the only module that imports the program.

It builds the program's model and serving configuration from a
configuration file and its architecture module (``archs/``), hands the
benchmark's weights to the program in the tree the module lays out,
checked against the program's own, and reads the engine's own counters.
Every ``ServeConfig`` field that the configuration file does not set stays
at the program's default, so a change to a default is measured.
"""
from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.params import abstract_params  # noqa: E402
from repro.serve import ServeConfig, ServeEngine  # noqa: E402

# the epsilon ``models/layers.py``'s ``apply_norm`` applies to each norm,
# where ``ModelConfig`` has no ``norm_eps`` field to take it from
PROGRAM_EPS = {"rmsnorm": 1e-6, "layernorm": 1e-5, "np_layernorm": 1e-5}


def model_config(conf: dict, arch):
    """The program's ModelConfig: the registry entry with every field the
    architecture module reads from the configuration file. The registry
    entry has to have the norm and activation that the module reads and
    that the file's ``program`` states, and the program has to honour the
    file's epsilon: from a ``norm_eps`` field where ``ModelConfig`` has
    one, and otherwise only at the value it applies."""
    base = get_arch(conf["program"]["arch"])
    fields = dict(arch.program_fields(conf))
    for key in ("norm", "act"):
        for stated in (fields[key], conf["program"][key]):
            if getattr(base, key) != stated:
                raise ValueError(
                    f"registry {base.name!r} has {key}="
                    f"{getattr(base, key)!r}; the configuration states "
                    f"{stated!r}")
    eps = fields.pop("norm_eps")
    if any(f.name == "norm_eps" for f in dataclasses.fields(base)):
        fields["norm_eps"] = eps
    elif eps != PROGRAM_EPS[base.norm]:
        raise ValueError(
            f"the configuration states {base.norm} eps {eps}; the program "
            f"applies {PROGRAM_EPS[base.norm]} and its ModelConfig has no "
            f"norm_eps field to take another")
    return dataclasses.replace(base, **fields)


def serve_config(conf: dict) -> ServeConfig:
    return ServeConfig(**conf["serve"])


def program_params(arch, w: dict, cfg) -> dict:
    """The benchmark's weights in the program's parameter tree (the
    architecture module's ``program_tree``), checked leaf by leaf against
    the program's own parameter definitions."""
    params = arch.program_tree(w)
    want = abstract_params(T.param_defs(cfg))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree no longer matches "
                         f"the benchmark's layout:\n{want}\nvs\n{got}")
    return params


def make_engine(cfg, scfg, params) -> ServeEngine:
    return ServeEngine(cfg, params, scfg)


def counters(eng) -> dict:
    """The engine's own counters, copied."""
    return {"dispatch": dict(eng.dispatch_counts),
            "prefill": dict(eng.prefill_stats),
            "host_syncs": int(eng.host_syncs)}


def queued_ids(eng) -> set:
    return {r.rid for r in eng.queue}


def slots(eng) -> dict:
    """{request id: slot} of the requests resident in the engine."""
    return {r.rid: i for i, r in enumerate(eng.slot_req) if r is not None}


def busy(eng) -> bool:
    """The engine holds queued or resident work."""
    return bool(eng.queue) or any(r is not None for r in eng.slot_req)
