"""Readings that set a cell's limit, on the chip: for each seed, one run of
the cell (weights, engine, warm-up, a window at the cell's own load) and
then the served tokens' widest gap below the float32 reference (the
program's reading) and the widest gap of the tokens the fp8 control puts
first at the same positions (the control's reading). Each reading goes
through the run's own check against ``limits/<cell>.json``: the program's
``correct`` and the control's, which has to come out false.

    python3 benchmarks/onchip/tools/control.py olmo-1b.sum 10 101 102 103

Arguments: the cell, the window in seconds, then the seeds. All seeds run
in this one process; each prints one JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import device  # noqa: E402


def main(cell_name: str, seconds: float, seeds) -> int:
    cell = cells.load(cell_name)
    devices = device.require_chip(cell.chips)
    device.use_compile_cache()
    import run
    for seed in seeds:
        t0 = time.perf_counter()
        out = run.run_cell(cell, seed, seconds, False, devices, t0,
                           control=True)
        print(json.dumps({
            "cell": cell_name, "seed": seed, "correct": out["correct"],
            "served_gap": out["checks"]["served_gap"]["value"],
            "control_correct": out["info"]["control"]["correct"],
            "control_gap": out["info"]["control"]["checks"]["served_gap"][
                "value"],
            "limit": out["checks"]["served_gap"]["limit"],
            "positions": out["info"]["positions"],
            "argmax_differs": out["info"]["argmax_differs"],
            "control_argmax_differs": out["info"]["control"][
                "argmax_differs"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "failed": out["failed"], "wall_s": time.perf_counter() - t0,
            "info": out["info"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], float(sys.argv[2]),
                  [int(s) for s in sys.argv[3:]]))
