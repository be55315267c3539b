"""Idle device time by the program's own host span, on the chip: one traced
run of a cell, then the idle device seconds of its traced part credited to
the innermost ``serve.*`` span open at the time.

    python3 benchmarks/onchip/tools/idle_by_span.py olmo-1b.gen 51 7

Arguments: the cell, the window in seconds, the seed. Prints one JSON line:
the split, the benchmark's own split by ``bench.*`` span for comparison,
the window's slowest engine steps as the program recorded them, and the
run's per-layer metrics.

The reduction is ``tracing.reduce_events`` unchanged, fed the
``bench.trace_open``/``bench.trace_close`` markers, ``bench.traffic`` (the
engine stood empty), and the ``serve.*`` spans of the same ``.xplane.pb``
cut into pieces named by the innermost span (``hostspans.innermost``).
Idle time inside no ``serve.*`` span is credited to ``bench.window``: the
driver's own code between engine steps.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import device  # noqa: E402

SERVE = "serve."


def serve_events(trace_dir: str):
    """[(name, start_s, end_s)] of the host's ``serve.*`` spans in the
    newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    return [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(SERVE)]


def slowest(win, hostspans, n: int = 8):
    """The window's ``n`` slowest engine steps as the program recorded
    them (milliseconds), beside what the driver saw them admit."""
    pairs = hostspans.align(win, hostspans.spans.latest())
    if pairs is None:
        return None
    ms = hostspans.MS
    return [{"at_s": st.start, "admitted": len(st.admitted),
             "kind": rec.kind, "wall": rec.wall_ns * ms,
             "self": {k: v * ms for k, v in rec.self_ns.items()},
             "gc": rec.gc_ns * ms, "gc_gap": rec.gc_gap_ns * ms,
             "compiles": rec.compiles}
            for st, rec in sorted(pairs, key=lambda p: -p[1].wall_ns)[:n]]


def main(cell_name: str, seconds: float, seed: int) -> int:
    cell = cells.load(cell_name)
    devices = device.require_chip(cell.chips)
    device.use_compile_cache()
    import driver
    import hostspans
    import run
    import tracing

    kept = {}
    load = tracing.load_events

    def load_and_keep(trace_dir):
        dev, host = load(trace_dir)
        marks = [h for h in host
                 if h[0] in (tracing.OPEN, tracing.CLOSE, tracing.IDLE_SPAN)]
        kept["events"] = (dev, marks + hostspans.innermost(
            serve_events(trace_dir)))
        return dev, host

    tracing.load_events = load_and_keep
    for name in ("run_open", "run_closed"):    # keep the window as well
        setattr(driver, name, lambda *a, _loop=getattr(driver, name):
                kept.setdefault("win", _loop(*a)))
    out = run.run_cell(cell, seed, seconds, True, devices, T0)
    red = tracing.reduce_events(*kept["events"],
                                slots=cell.conf["serve"]["max_slots"])
    print(json.dumps({
        "cell": cell_name, "seed": seed, "correct": out["correct"],
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_work_s": red["idle_work_s"],
        "idle_by_span": dict(sorted(red["idle_by_span"].items(),
                                    key=lambda kv: -kv[1])),
        "bench_idle_gaps": out["breakdown"]["idle_gaps"],
        "slowest_steps": slowest(kept["win"], hostspans),
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], float(sys.argv[2]), int(sys.argv[3])))
