"""Spread of each metric over sets of runs of one cell, as the bounds in
``BENCHMARK.json`` are set from it.

    python3 benchmarks/onchip/tools/spread.py DIR

``DIR`` holds one file per run, ``<set>.<seed>.out``, whose last line is
the run's result. For every set of three runs or more and every metric it
prints the median, the spread (first to third quartile by
``statistics.quantiles(values, n=4)``, over the median), the spread with
the run farthest from the median left out, and the spread of all the
runs of every set together. It also lists each run's correctness check.
"""
from __future__ import annotations

import glob
import json
import os
import statistics
import sys
from collections import defaultdict


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread(values[:far] + values[far + 1:])


def load(directory):
    runs = defaultdict(dict)
    for path in sorted(glob.glob(os.path.join(directory, "*.out"))):
        label = os.path.basename(path)[:-len(".out")]
        lines = [ln for ln in open(path).read().splitlines()
                 if ln.startswith("{")]
        runs[label.split(".")[0]][label] = (json.loads(lines[-1])
                                            if lines else None)
    return runs


def main(directory):
    runs = load(directory)
    pooled = defaultdict(list)
    for name, group in sorted(runs.items()):
        for label, r in group.items():
            if r is None:
                print(f"{label}: no result")
                continue
            print(f"{label}: correct {r['correct']} "
                  f"{json.dumps(r.get('checks'))}")
        done = [r for r in group.values() if r is not None]
        if len(done) < 3:
            continue
        for metric in done[0]["metrics"]:
            v = [r["metrics"][metric]["value"] for r in done]
            pooled[metric] += v
            print(f"set {name} {metric}: median {statistics.median(v):.6g} "
                  f"spread {spread(v):.4f} without the farthest "
                  f"{trimmed(v):.4f} runs {[round(x, 4) for x in v]}")
    for metric, v in pooled.items():
        print(f"all sets {metric}: spread {spread(v):.4f} over {len(v)} runs")


if __name__ == "__main__":
    main(sys.argv[1])
