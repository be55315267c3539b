"""Cells, found by name: ``BENCHMARK.json`` names a cell's configuration
and traffic; each lives in a file of its own under this directory.

    configs/<config>.json   sizes as run, source, cuts, serve sizes
    traffic/<mix>.json      the traffic mix (see traffic.py)
    limits/<cell>.json      the limit of each number the check compares
    metrics/<metric>.py     a reader per metric (see readers.py)

``rehearsal=True`` shrinks a cell for a CPU rehearsal: the same files and
code path, at tiny widths and short lengths. The measurement command never
asks for it.
"""
from __future__ import annotations

import copy
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))

REHEARSAL_CONFIG = {"hidden_size": 256, "intermediate_size": 512,
                    "num_hidden_layers": 2, "num_attention_heads": 4,
                    "vocab_size": 512}
REHEARSAL_SERVE = {"max_slots": 4, "max_len": 256, "prefill_chunk": 64}
REHEARSAL_MIX = {
    "prompt": {"dist": "lognormal", "mean": 60, "sigma": 0.8, "clip": [2, 180]},
    "output": {"dist": "lognormal", "mean": 16, "sigma": 0.8, "clip": [2, 48]}}
# open-loop arrivals per second in a rehearsal: enough that requests
# overlap in the engine's slots, as they do at the cells' own sizes
REHEARSAL_RATE = 8.0
# the served-gap limit at rehearsal sizes, whose logits are smaller than
# the cells' own: sound rehearsals read at most 0.0129 and the fp8
# control at least 0.0684 over seeds 101-112 of both cells
REHEARSAL_GAP_LIMIT = 0.045


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict              # configs/<config>.json
    mix: dict               # traffic/<mix>.json
    limits: dict            # limits/<cell>.json
    bench: dict             # BENCHMARK.json


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def names() -> list:
    """Every cell ``BENCHMARK.json`` lists."""
    return [w["name"] for w in _read(CHECKOUT, "BENCHMARK.json")["workloads"]]


def load(name: str, rehearsal: bool = False) -> Cell:
    bench = _read(CHECKOUT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = _read(CHECKOUT, confs[w["config"]]["file"])
    mix = _read(HERE, "traffic", f"{w['traffic']}.json")
    limits = _read(HERE, "limits", f"{name}.json")
    if rehearsal:
        conf, mix = copy.deepcopy(conf), copy.deepcopy(mix)
        kv = conf["config"]["num_key_value_heads"]
        heads = conf["config"]["num_attention_heads"]
        conf["config"].update(REHEARSAL_CONFIG)
        conf["config"]["num_key_value_heads"] = 4 if kv == heads else 2
        conf["config"]["torch_dtype"] = "bfloat16"
        conf["serve"] = dict(REHEARSAL_SERVE)
        mix.update(copy.deepcopy(REHEARSAL_MIX))
        if mix["loop"] == "closed":
            mix["clients"] = REHEARSAL_SERVE["max_slots"]
        else:
            mix["rate_per_s"] = REHEARSAL_RATE
        limits = copy.deepcopy(limits)
        limits["served_gap"]["limit"] = REHEARSAL_GAP_LIMIT
    return Cell(name, int(w["chips"]), conf, mix, limits, bench)
