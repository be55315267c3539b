"""The system under test, and the only module that imports the program.

It builds the program's model and serving configuration from a
configuration file, hands the benchmark's weights to the program in the
layout the program expects, and reads the engine's own counters. Every
``ServeConfig`` field that the configuration file does not set stays at
the program's default, so a change to a default is measured.
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

from weights import dims  # noqa: E402


def model_config(conf: dict):
    """The program's ModelConfig: the registry entry with every size taken
    from the configuration file."""
    base = get_arch(conf["program"]["arch"])
    for key in ("norm", "act"):
        if getattr(base, key) != conf["program"][key]:
            raise ValueError(
                f"registry {base.name!r} has {key}={getattr(base, key)!r}; "
                f"the configuration states {conf['program'][key]!r}")
    m = dims(conf["config"])
    return dataclasses.replace(
        base, num_layers=m["layers"], d_model=m["d"], num_heads=m["heads"],
        num_kv_heads=m["kv_heads"], head_dim=m["hd"], d_ff=m["ff"],
        vocab_size=m["vocab"], rope_theta=m["theta"],
        tie_embeddings=m["tied"], dtype=conf["config"]["torch_dtype"])


def serve_config(conf: dict) -> ServeConfig:
    return ServeConfig(**conf["serve"])


def program_params(w: dict, cfg) -> dict:
    """The benchmark's weights in the program's parameter tree, checked
    leaf by leaf against the program's own parameter definitions."""
    L = w["layers"]
    block = {
        "attn": {"wq": L["wq"], "wk": L["wk"], "wv": L["wv"], "wo": L["wo"]},
        "ffn": {"wg": L["w_gate"], "wi": L["w_up"], "wo": L["w_down"]},
        "norm1": {}, "norm2": {},
    }
    embed = {"tok": w["embed"]}
    if "lm_head" in w:
        embed["lm_head"] = w["lm_head"]
    params = {"embed": embed, "blocks": {"pos0": block}, "final_norm": {}}
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


def busy(eng) -> bool:
    """The engine holds queued or resident work."""
    return bool(eng.queue) or any(r is not None for r in eng.slot_req)
