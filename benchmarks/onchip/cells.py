"""Cells, found by name: ``BENCHMARK.json`` names a cell's configuration
and traffic; each lives in a file of its own under this directory.

    configs/<config>.json   sizes as run, source, cuts, serve sizes, and
                            its ``architecture``
    archs/<architecture>.py weight layout, reference and counts of an
                            architecture (see archs/__init__.py)
    traffic/<mix>.json      the traffic mix (see traffic.py)
    limits/<cell>.json      the limit of each number the check compares
    metrics/<metric>.py     a reader per metric (see readers.py)

``rehearsal=True`` shrinks a cell for a CPU rehearsal: the same files and
code path, at tiny widths and short lengths. The measurement command never
asks for it.
"""
from __future__ import annotations

import copy
import dataclasses
import importlib
import json
import os
import re
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))

REHEARSAL_SERVE = {"max_slots": 4, "max_len": 256, "prefill_chunk": 64}
REHEARSAL_MIX = {
    "prompt": {"dist": "lognormal", "mean": 60, "sigma": 0.8, "clip": [2, 180]},
    "output": {"dist": "lognormal", "mean": 16, "sigma": 0.8, "clip": [2, 48]}}
# open-loop arrivals per second in a rehearsal: enough that requests
# overlap in the engine's slots, as they do at the cells' own sizes
REHEARSAL_RATE = 8.0
# the served-gap limit at rehearsal sizes, whose logits are smaller than
# the cells' own: sound rehearsals read at most 0.0129 and the fp8
# control at least 0.0723 over seeds 101-112 of both cells (the RMSNorm
# fixture of tests/data: at most 0.0314 and at least 0.2265)
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
    cell = Cell(name, int(w["chips"]), conf, mix, limits, bench)
    return shrink(cell) if rehearsal else cell


def arch(conf: dict):
    """The module ``archs/<architecture>.py`` that a configuration file
    names, which knows everything that depends on the architecture."""
    name = conf["architecture"]
    if not re.fullmatch(r"[A-Za-z0-9_]+", name) or not os.path.isfile(
            os.path.join(HERE, "archs", f"{name}.py")):
        raise KeyError(f"no architecture {name!r} in archs/")
    return importlib.import_module(f"archs.{name}")


def shrink(cell: Cell) -> Cell:
    """``cell`` at rehearsal sizes: its architecture's small model, few
    slots, short requests, and the rehearsal limit."""
    conf = arch(cell.conf).rehearsal(cell.conf)
    conf["serve"] = dict(REHEARSAL_SERVE)
    mix = copy.deepcopy(cell.mix)
    mix.update(copy.deepcopy(REHEARSAL_MIX))
    if mix["loop"] == "closed":
        mix["clients"] = REHEARSAL_SERVE["max_slots"]
    else:
        mix["rate_per_s"] = REHEARSAL_RATE
    limits = copy.deepcopy(cell.limits)
    limits["served_gap"]["limit"] = REHEARSAL_GAP_LIMIT
    return dataclasses.replace(cell, conf=conf, mix=mix, limits=limits)
