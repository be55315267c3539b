"""The program's own step records, for the per-layer metrics that read
host time inside ``ServeEngine``.

The second module that imports the program (``system.py`` is the first).
It reads ``repro.obs.spans``: the engine keeps a ring of one record per
``engine.step()`` (host time by ``serve.*`` span, GC pauses, compiles),
and ``spans.latest()`` keeps the newest engine's ring after ``run.py`` has
freed the engine. A program without ``repro.obs.spans`` has no records:
every reader here then returns None, and its metric is left out.

The records are aligned with ``run.win.steps`` by position from the end:
no engine step runs after the window, and every step in it goes through
``driver._step``. Alignment fails, and the readers return None, when the
ring did not hold the whole window or the records' step indices skip.
"""
from __future__ import annotations

import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

try:
    from repro.obs import spans
except ImportError:          # a program from before the spans
    spans = None

MS = 1e-6                    # milliseconds per nanosecond


def align(win, log) -> Optional[list]:
    """[(driver Step, StepRecord)] for every step of the window, or None
    when ``log`` did not keep them all or its step indices skip."""
    if log is None or not win.steps or len(log.steps) < len(win.steps):
        return None
    recs = list(log.steps)[-len(win.steps):]
    if any(b.step != a.step + a.ticks for a, b in zip(recs, recs[1:])):
        return None
    return list(zip(win.steps, recs))


def traced_records(run) -> Optional[list]:
    """The records of the steps in ``run.steps()``: the traced part of the
    window, or the whole window in an untraced run."""
    pairs = align(run.win, spans.latest() if spans else None)
    if pairs is None:
        return None
    keep = {id(st) for st in run.steps()}
    return [rec for st, rec in pairs if id(st) in keep]


def gc_pause_ms(run) -> Optional[float]:
    """GC pause milliseconds inside the traced part's steps and in the
    gaps before them. Shared by the ``gc_pause_ms.*`` metrics."""
    recs = traced_records(run)
    if not recs:
        return None
    return MS * sum(r.gc_ns + r.gc_gap_ns for r in recs)


def innermost(events: List[tuple]) -> List[tuple]:
    """Properly nested host spans ``[(name, start, end)]`` cut into pieces
    that do not overlap, each named by the innermost span open in it."""
    out, stack, t = [], [], 0.0
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            out.append((n, t, end))
            t = end
        if stack:
            out.append((stack[-1][0], t, s))
        stack.append((name, e))
        t = s
    while stack:
        n, end = stack.pop()
        out.append((n, t, end))
        t = end
    return [p for p in out if p[2] > p[1]]
