"""From a profiler trace to the numbers the per-layer metrics read.

A traced run records one JAX profiler trace over part of its window,
between the host markers ``bench.trace_open`` and ``bench.trace_close``.
The reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
and keeps:

  window_s      the time between the two markers
  busy_s        the union of the intervals in which an operation ran on the
                device ("XLA Ops" line) inside it, averaged over devices
  idle_work_s   idle device time outside ``bench.traffic`` spans, i.e.
                while the engine held queued or resident work
  programs      per program execution name ("XLA Modules" line): its role,
                executions and device seconds
  idle_by_span  idle device seconds by the ``bench.*`` host span open at
                the time (``bench.window`` when none)

Program roles. The engine jits ``functools.partial`` objects, which XLA
names ``jit__unknown(<id>)``, so a step program's name does not say what
it is. A decode-carrying program (decode, superstep, fused step) returns
the engine's int32 fetch of (token, done, length) per slot, shape (3, B) or
(k, 3, B); an engine program without it is a prefill program. Other names
(``jit_scatter``, ...) are eager operations, role "other".

Host and device events share the trace's clock, which the profiler
aligns when it writes the trace.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OPEN, CLOSE = "bench.trace_open", "bench.trace_close"
WINDOW_SPAN = "bench.window"
IDLE_SPAN = "bench.traffic"
ENGINE_PROGRAM = "jit__unknown("

Interval = Tuple[float, float]


def fetch_pattern(slots: int):
    """An HLO result type of the engine's decode fetch: s32[3,B] or
    s32[k,3,B]."""
    return re.compile(r"= s32\[(\d+,)?3,%d\]" % slots)


def role(name: str, op_texts: List[str], slots: int) -> str:
    if not name.startswith(ENGINE_PROGRAM):
        return "other"
    pat = fetch_pattern(slots)
    return "decode" if any(pat.search(t) for t in op_texts) else "prefill"


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def complement(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce_events(device: Dict[str, List[tuple]], host: List[tuple],
                  slots: int) -> dict:
    """The reduction, on plain events.

    ``device``: {device plane name: [(line, name, start_s, end_s), ...]};
    ``host``: [(name, start_s, end_s), ...] of the ``bench.*`` spans;
    ``slots``: the engine's slot count (the fetch's width)."""
    marks = {n: s for n, s, _ in host if n in (OPEN, CLOSE)}
    if OPEN not in marks or CLOSE not in marks:
        raise ValueError(f"the trace lacks the {OPEN}/{CLOSE} markers")
    lo, hi = marks[OPEN], marks[CLOSE]
    spans = defaultdict(list)
    for n, s, e in host:
        if n not in (OPEN, CLOSE, WINDOW_SPAN):
            spans[n].append((s, e))
    spans = {n: union(clip(v, lo, hi)) for n, v in spans.items()}
    programs: Dict[str, dict] = {}
    busy_s = idle_work_s = 0.0
    idle_by_span: Dict[str, float] = defaultdict(float)
    for events in device.values():
        ops = sorted((s, e, name) for line, name, s, e in events
                     if line == OPS_LINE)
        starts = [o[0] for o in ops]
        for line, name, s, e in events:
            if line != MODULES_LINE or s < lo or e > hi:
                continue
            if name not in programs:
                i = bisect.bisect_left(starts, s)
                j = bisect.bisect_right(starts, e)
                texts = [o[2] for o in ops[i:j]]
                programs[name] = {"role": role(name, texts, slots),
                                  "n": 0, "device_s": 0.0}
            programs[name]["n"] += 1
            programs[name]["device_s"] += e - s
        busy = union(clip([(o[0], o[1]) for o in ops], lo, hi))
        busy_s += sum(e - s for s, e in busy)
        gaps = complement(busy, lo, hi)
        idle = sum(e - s for s, e in gaps)
        idle_work_s += idle - overlap(gaps, spans.get(IDLE_SPAN, []))
        rest = idle
        for n, iv in spans.items():
            x = overlap(gaps, iv)
            idle_by_span[n] += x
            rest -= x
        idle_by_span[WINDOW_SPAN] += max(rest, 0.0)
    n_dev = max(len(device), 1)
    for p in programs.values():
        p["device_s"] /= n_dev
    return {
        "window_s": hi - lo,
        "busy_s": busy_s / n_dev,
        "idle_work_s": idle_work_s / n_dev,
        "devices": len(device),
        "programs": programs,
        "idle_by_span": {k: v / n_dev for k, v in idle_by_span.items()},
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: device seconds by program (named
    by role and execution name) and idle seconds by host span."""
    progs = sorted(((f"{p['role']}:{name}", p["device_s"])
                    for name, p in red["programs"].items()),
                   key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [list(kv) for kv in progs],
            "idle_gaps": [list(kv) for kv in idle]}


def load_events(trace_dir: str):
    """(device, host) events of the newest ``.xplane.pb`` under
    ``trace_dir``, times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            evs = []
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs += [(line.name, e.name, e.start_ns * 1e-9,
                             e.end_ns * 1e-9) for e in line.events]
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                         for e in line.events if e.name.startswith("bench.")]
    return device, host
