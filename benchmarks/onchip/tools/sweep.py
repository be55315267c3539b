"""Knee sweep of an open-loop cell on the chip: one engine, one window per
offered rate, and how the backlog behaves at each.

    python3 benchmarks/onchip/tools/sweep.py olmo-1b.sum 15 2 3 4 6 8

Arguments: the cell, the window in seconds, then the rates (requests/s).
For each rate it prints the TTFT median and p95 (ms), the p95 of the
window's first and second half, the requests still queued when the window
closed, and how long the engine took to drain after it. The knee is the
highest rate whose backlog does not grow over the window: the second
half's TTFT no worse than the first's and a drain of about one request's
service time.
"""
from __future__ import annotations

import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import cells  # noqa: E402
import device  # noqa: E402


def main(cell_name: str, seconds: float, rates) -> int:
    cell = cells.load(cell_name)
    devices = device.require_chip(cell.chips)
    device.use_compile_cache()
    import driver
    import system
    import traffic
    import weights
    conf = cell.conf
    arch = cells.arch(conf)
    cfg = system.model_config(conf, arch)
    w = weights.make_weights(arch.shapes(conf), 1)
    eng = system.make_engine(cfg, system.serve_config(conf),
                             system.program_params(arch, w, cfg))
    driver.warm_up(eng, traffic.longest_prompt(cell.mix), cfg.vocab_size)
    print(f"[sweep] {cell_name}: set-up {time.perf_counter() - T0:.1f} s",
          flush=True)
    rows = []
    for rate in rates:
        mix = dict(cell.mix, rate_per_s=rate)
        sched = traffic.build(mix, 11, seconds, cfg.vocab_size)
        win = driver.run_open(eng, sched, seconds,
                              driver.Tracer(None, 0))
        recs = [r for r in win.records.values() if r.times]
        ttft = np.array([r.times[0] - r.due for r in recs]) * 1e3
        half = np.array([r.due < seconds / 2 for r in recs])
        queued = sum(1 for r in win.records.values()
                     if not np.isnan(r.left_queue) and r.left_queue > seconds)
        row = {"rate": rate, "requests": len(win.records),
               "ttft_p50": float(np.median(ttft)),
               "ttft_p95": float(np.percentile(ttft, 95)),
               "p95_first_half": float(np.percentile(ttft[half], 95)),
               "p95_second_half": float(np.percentile(ttft[~half], 95)),
               "admitted_after_close": queued,
               "drain_s": win.end - seconds, "failed": win.failed}
        rows.append(row)
        print("[sweep] " + json.dumps(row), flush=True)
    print(json.dumps({"cell": cell_name, "seconds": seconds, "rows": rows,
                      "device": devices[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], float(sys.argv[2]),
                  [float(r) for r in sys.argv[3:]]))
