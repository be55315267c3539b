"""Rehearse a cell end to end on the CPU at rehearsal sizes: the harness's
whole run (weights, engine, warm-up, window, metrics, reference check)
with the look for a chip skipped.

    JAX_PLATFORMS=cpu python3 benchmarks/onchip/tests/rehearse.py \\
        olmo-1b.sum --seed 3 --seconds 3 [--trace 1]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import cells  # noqa: E402
import run  # noqa: E402


def rehearse(cell, seed: int, seconds: float, trace: bool = False,
             control: bool = False) -> dict:
    """One rehearsal run of ``cell``: a name from ``BENCHMARK.json``, or a
    ``cells.Cell`` already at rehearsal sizes (``cells.shrink``)."""
    if isinstance(cell, str):
        cell = cells.load(cell, rehearsal=True)
    return run.run_cell(cell, seed, seconds, trace, jax.devices(),
                        time.perf_counter(), control=control)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0)
    a = ap.parse_args()
    print(json.dumps(rehearse(a.workload, a.seed, a.seconds, bool(a.trace))))
