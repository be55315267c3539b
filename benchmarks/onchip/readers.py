"""Metric readers: ``metrics/<name>.py`` defines ``read(run)``, returning
the metric's value or None where the run holds nothing to read. A later
PR adds a metric by adding a file and a ``BENCHMARK.json`` entry.

``Run`` is what a reader sees: the window's client-side records, the
engine's counters at the window's edges, the reduced trace of a traced
run, the operation and byte counts of the configuration and the chip's
peaks.
"""
from __future__ import annotations

import importlib.util
import os
from typing import List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Run:
    def __init__(self, win, setup_s, counters, trace, counts, peaks):
        self.win = win
        self.seconds = win.seconds
        self.setup_s = setup_s
        self.counters = counters      # {"start": ..., "end": ...}
        self.trace = trace            # tracing.reduce_events(...) or None
        self.counts = counts          # flops.Counts
        self.peaks = peaks

    # ---- client side ---------------------------------------------------- #
    def requests(self):
        """Requests that count: in an open loop every one due in the
        window, in a closed loop every one sent in it."""
        recs = self.win.records.values()
        if self.win.loop == "open":
            return [r for r in recs if r.due < self.seconds]
        return list(recs)

    def token_times(self, r) -> List[float]:
        """Delivery times of a request's tokens that count: all of them in
        an open loop, those inside the window in a closed loop."""
        if self.win.loop == "open":
            return r.times
        return [t for t in r.times if t <= self.seconds]

    def token_gaps(self) -> Tuple[np.ndarray, np.ndarray]:
        """Seconds between consecutive counted tokens of each request, and
        for each gap whether a step that admitted a request lies in it (an
        admission gap: the request waited on another's prefill) or not
        (a decode gap)."""
        steps = self.win.steps
        admits = np.cumsum([bool(st.admitted) for st in steps])
        at = {tok: i for i, st in enumerate(steps) for tok in st.tokens}
        gaps, admitting = [], []
        for r in self.requests():
            t = self.token_times(r)
            for j in range(1, len(t)):
                gaps.append(t[j] - t[j - 1])
                admitting.append(admits[at[r.rid, j]]
                                 > admits[at[r.rid, j - 1]])
        return np.array(gaps), np.array(admitting, bool)

    def counter_delta(self, group: str, key: Optional[str] = None) -> int:
        a, b = self.counters["start"][group], self.counters["end"][group]
        return b - a if key is None else b[key] - a[key]

    # ---- device trace ------------------------------------------------- #
    def program_time(self, role: str) -> Tuple[int, float]:
        """(executions, device seconds) in the traced window of the engine
        programs of ``role`` ("prefill" or "decode", see tracing.py);
        (0, 0.0) without a trace."""
        if self.trace is None:
            return 0, 0.0
        n = s = 0
        for v in self.trace["programs"].values():
            if v["role"] == role:
                n += v["n"]
                s += v["device_s"]
        return n, s

    def steps(self):
        """The engine steps of the traced part of the window, or of the
        whole window in an untraced run."""
        if self.win.traced is None:
            return self.win.steps
        lo, hi = self.win.traced
        return [st for st in self.win.steps if st.start >= lo and st.end <= hi]

    def prefill_work(self) -> Tuple[int, float]:
        """(valid prompt tokens, operations) prefilled in ``steps()``: the
        prompts of the requests admitted in them (a serial engine step
        prefills the whole admission wave)."""
        tokens, flops = 0, 0.0
        for st in self.steps():
            for rid in st.admitted:
                n = len(self.win.records[rid].prompt) - 1
                tokens += n
                flops += self.counts.prefill_flops(n)
        return tokens, flops

    def decode_work(self) -> Tuple[float, float, int]:
        """(operations, bytes, steps) of the decode steps in ``steps()``."""
        flops = byts = 0.0
        steps = 0
        for st in self.steps():
            if not st.tokens:
                continue
            ctxs = [len(self.win.records[rid].prompt) + j
                    for rid, j in st.tokens]
            flops += sum(self.counts.decode_flops(c) for c in ctxs)
            byts += self.counts.decode_step_bytes(ctxs)
            steps += 1
        return flops, byts, steps

    def roofline_pct(self, flops: float, byts: float,
                     device_s: float) -> Optional[float]:
        if device_s <= 0:
            return None
        least = max(flops / self.peaks["bf16_flops_per_s"],
                    byts / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / device_s


def p95(values) -> Optional[float]:
    return float(np.percentile(values, 95)) if len(values) else None


def mfu_pct(run: Run) -> Optional[float]:
    """Operations of every token processed in the traced window, over the
    window times the chip's peak. Shared by the ``mfu_pct.*`` metrics."""
    if run.trace is None:
        return None
    _, prefill = run.prefill_work()
    decode, _, _ = run.decode_work()
    return 100.0 * (prefill + decode) / (
        run.trace["window_s"] * run.peaks["bf16_flops_per_s"])


def device_idle_pct(run: Run) -> Optional[float]:
    """Idle device time while the engine held work, as a share of the
    traced window. Shared by the ``device_idle_pct.*`` metrics."""
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_work_s"] / run.trace["window_s"]


def read(name: str, run: Run):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)

