"""On-chip benchmark: one run of one cell.

    python3 benchmarks/onchip/run.py --workload olmo-1b.sum --seed 7 \\
        --seconds 30 --trace 0

Runs in one process on the chips of this machine and exits 2, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for. The
run: make the configuration's weights from the seed on the device, build
the engine, warm up every shape the cell's traffic reaches (set-up ends
here), serve the seed's traffic for ``--seconds`` through
``ServeEngine.add_request`` and ``ServeEngine.step``, read the peak device
memory, free the engine, and hold a sample of the finished requests to the
float32 reference (``reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics from a profiled run), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number the
check compared, beside its limit. The same numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import device  # noqa: E402

TRACE_SECONDS = 8.0        # a --trace 1 run profiles the window's last 8 s


def sample_finished(win, rows: int, slots: int, seed: int):
    """The requests the check compares, ``rows`` in all: the longest
    finished one, then one from each part of the batch that the sample
    does not hold yet (even and odd slots, in the lower and the upper
    half of the ``slots``), then any others, each drawn in an order from
    the seed. A fault confined to part of the batch, such as a step that
    leaves every other slot out, shows in the sample whenever a request
    of that part finished."""
    import numpy as np
    done = [r for r in win.records.values() if r.done and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng((seed & (2**64 - 1)) ^ 0x5EED)
    order = [rest[i] for i in rng.permutation(len(rest))]

    def part(r):
        return None if r.slot < 0 else (r.slot % 2, 2 * r.slot // slots)

    picked, held = [], {part(longest)}
    for r in order:
        if len(picked) < rows - 1 and part(r) is not None \
                and part(r) not in held:
            picked.append(r)
            held.add(part(r))
    chosen = {r.rid for r in picked}
    picked += [r for r in order
               if r.rid not in chosen][:rows - 1 - len(picked)]
    return [longest] + sorted(picked, key=lambda r: r.rid)


def judge(gap, failed: int, limits: dict):
    """``correct`` and the numbers it compared, each beside its limit."""
    checks = {
        "served_gap": {"value": gap, "limit": limits["served_gap"]["limit"]},
        "failed": {"value": failed, "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, control: bool = False) -> dict:
    """One run of ``cell``; returns the result object (without printing).
    ``control`` also puts the fp8 control in the program's place: the
    tokens it would serve at the same positions go through the same
    ``judge`` against the cell's limits, under ``info.control``
    (``tools/control.py``; the benchmark's own runs never do)."""
    import jax
    import numpy as np

    import driver
    import readers
    import reference
    import system
    import tracing
    import traffic
    import weights

    conf, mix = cell.conf, cell.mix
    arch = cells.arch(conf)
    cfg = system.model_config(conf, arch)
    scfg = system.serve_config(conf)
    w = weights.make_weights(arch.shapes(conf), seed)
    params = system.program_params(arch, w, cfg)
    eng = system.make_engine(cfg, scfg, params)
    driver.warm_up(eng, traffic.longest_prompt(mix), cfg.vocab_size)
    sched = traffic.build(mix, seed, seconds, cfg.vocab_size)
    log = device.CompileLog()
    trace_dir = tempfile.mkdtemp(prefix="onchip-trace-") if trace else None
    tracer = driver.Tracer(trace_dir, max(0.0, seconds - TRACE_SECONDS))
    before = system.counters(eng)
    setup_s = time.perf_counter() - t_start
    loop = driver.run_open if mix["loop"] == "open" else driver.run_closed
    win = loop(eng, sched, seconds, tracer)
    after = system.counters(eng)
    window_compiles = log.count()
    peak = device.memory_peak(devices)
    del eng, params
    gc.collect()

    red = None
    if trace:
        red = tracing.reduce_events(*tracing.load_events(trace_dir),
                                    scfg.max_slots)
        shutil.rmtree(trace_dir, ignore_errors=True)
    on_chip = devices[0].platform == "tpu"
    run = readers.Run(win, setup_s, {"start": before, "end": after},
                      red if on_chip else None, arch.Counts(conf),
                      device.peaks(devices[0].device_kind) if on_chip else {})
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.bench[kind]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        v = readers.read(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    check = mix["check"]
    picked = sample_finished(win, check["requests"], scfg.max_slots, seed)
    gap = reference.served_gap(
        arch.logits_at, conf, w,
        [(r.prompt, np.asarray(r.tokens, np.int32)) for r in picked],
        rows=check["requests"], seq_len=scfg.max_len,
        positions=traffic.quantile_lengths(mix["output"],
                                           np.array([1 - 1e-12]))[0],
        control=control)
    correct, checks = judge(gap["served_gap"] if picked else None,
                            win.failed, cell.limits)
    dev = devices[0]
    out = {"correct": correct,
           "attempted": len(win.records) + len(win.errors),
           "failed": win.failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "memory_peak_bytes": peak}}
    if red is not None:
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = tracing.breakdown(red)
    out["info"] = {"window_compiles": window_compiles,
                   "sampled": len(picked), "positions": gap["positions"],
                   "argmax_differs": gap["argmax_differs"],
                   "late_p95_ms": _late_p95_ms(win),
                   "drained_s": win.end - seconds,
                   "plain_steps_ms": _plain_steps_ms(win)}
    out["checks"] = checks
    if trace:
        out["info"]["trace_counters"] = win.trace_counters
        out["info"]["profiler_stall_s"] = win.profiler_stall_s
    if control:
        c_correct, c_checks = judge(gap["control_gap"] if picked else None,
                                    win.failed, cell.limits)
        out["info"]["control"] = {
            "correct": c_correct, "checks": c_checks,
            "argmax_differs": gap["control_argmax_differs"]}
    return out


def _late_p95_ms(win):
    """How late the generator sent requests, p95 (open loop)."""
    import numpy as np
    late = [r.sent - r.due for r in win.records.values()]
    return float(np.percentile(late, 95)) * 1e3 if late else 0.0


def _plain_steps_ms(win):
    """Host milliseconds of the steps that admitted nothing: median, p99,
    max, and how many took over twice the median (a stall that no
    prefill explains)."""
    import numpy as np
    d = np.array([s.end - s.start for s in win.steps if not s.admitted])
    if not len(d):
        return None
    med = float(np.median(d))
    return {"p50": med * 1e3, "p99": float(np.percentile(d, 99)) * 1e3,
            "max": float(d.max()) * 1e3, "over_2x": int((d > 2 * med).sum()),
            "n": int(len(d))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.load(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return device.fail(str(e))
    try:
        devices = device.require_chip(cell.chips)
    except device.NoChip as e:
        return device.fail(str(e))
    print(f"[bench] compile cache: {device.use_compile_cache()}",
          file=sys.stderr, flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   T_START)
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
