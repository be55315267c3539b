"""Drives the engine: warm-up, then the measured window.

The window calls ``ServeEngine.add_request`` and ``ServeEngine.step`` and
nothing else of the program. Each call sits in a host span
(``jax.profiler.TraceAnnotation``) so that a traced run can say what the
host was doing while the device idled:

    bench.window       the whole window (and, in an open loop, its drain)
    bench.step         one ``engine.step()``
    bench.add_request  one ``engine.add_request()``
    bench.traffic      the engine is empty and the generator waits for the
                       next arrival
    bench.trace_open, bench.trace_close
                       markers at the edges of a traced run's profile

Open loop: a request is sent once the host clock passes its due time, and
every latency counts from the due time, so a slow step delays the
requests due during it. After the window closes, the engine drains the
requests due in it (at most ``DRAIN_S`` seconds). Closed loop: each client
sends its first request when the window opens and its next one as soon
as the last has finished; the window ends after ``seconds``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np
from jax.profiler import TraceAnnotation

import system
from traffic import Req, Schedule

DRAIN_S = 60.0


@dataclass
class Record:
    """One request as the client saw it (seconds after the window opened)."""
    rid: int
    prompt: np.ndarray
    due: float
    sent: float
    handle: object                   # the engine's Request (for .done)
    left_queue: float = float("nan")  # start of the step that admitted it
    slot: int = -1                   # its engine slot (-1: not seen in one)
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)

    @property
    def done(self) -> bool:
        return bool(self.handle.done)


@dataclass
class Step:
    start: float
    end: float
    admitted: List[int]
    tokens: List[tuple]              # (rid, index of the token in its reply)


class Window:
    """What one measured window produced."""

    def __init__(self, seconds: float, loop: str):
        self.seconds = seconds
        self.loop = loop
        self.records: Dict[int, Record] = {}
        self.steps: List[Step] = []
        self.end = 0.0               # when stepping stopped (open: drained)
        self.failed = 0
        self.errors: List[str] = []
        self.traced = None           # (open, close) host times of the profile
        self.trace_counters = None   # engine counters at (open, close)
        self.profiler_stall_s = {}   # seconds cut out for the profiler


class Tracer:
    """Profiles a window from ``start`` (seconds after it opened) until the
    caller says ``last``, switched between engine steps. Starting and
    stopping the profiler stall the host for seconds. ``poll`` returns the
    seconds it stalled, and the loops move their clock on by as much, so
    the stall is cut out of every time the window records: a traced run
    measures the engine, not the profiler. The loops stop it once nothing
    measured can wait on it: at the end of a closed-loop window, and in an
    open loop once every request due in the window has its first token.
    ``None`` directory: no-op."""

    def __init__(self, trace_dir, start: float):
        self.dir, self.start = trace_dir, start
        self.state = "idle" if trace_dir else "off"

    def poll(self, eng, win: "Window", now: float,
             last: bool = False) -> float:
        import jax
        t = time.perf_counter()
        if self.state == "idle" and now >= self.start and not last:
            jax.profiler.start_trace(self.dir)
            with TraceAnnotation("bench.trace_open"):
                pass
            self.state = "on"
            self._open = (now, system.counters(eng))
            win.profiler_stall_s["start"] = time.perf_counter() - t
            return win.profiler_stall_s["start"]
        if self.state == "on" and last:
            with TraceAnnotation("bench.trace_close"):
                pass
            win.traced = (self._open[0], now)
            win.trace_counters = (self._open[1], system.counters(eng))
            jax.profiler.stop_trace()
            self.state = "done"
            win.profiler_stall_s["stop"] = time.perf_counter() - t
            return win.profiler_stall_s["stop"]
        return 0.0


def _send(eng, win: Window, req: Req, due: float, t0: float) -> None:
    with TraceAnnotation("bench.add_request"):
        try:
            rid = eng.add_request(req.prompt, max_new_tokens=req.max_new)
        except ValueError as e:          # refused: counts as failed
            win.failed += 1
            win.errors.append(str(e))
            return
    win.records[rid] = Record(rid, req.prompt, due,
                              time.perf_counter() - t0, eng.queue[-1])


def _step(eng, win: Window, t0: float) -> List[int]:
    """One engine step; returns the request ids that finished in it."""
    queued = system.queued_ids(eng)
    start = time.perf_counter() - t0
    with TraceAnnotation("bench.step"):
        out = eng.step()
    end = time.perf_counter() - t0
    left = sorted(queued - system.queued_ids(eng))
    slots = system.slots(eng) if left else {}
    for rid in left:
        win.records[rid].left_queue = start
        win.records[rid].slot = slots.get(rid, -1)
    toks = []
    finished = []
    for rid, tok in out:
        rec = win.records[rid]
        rec.times.append(end)
        rec.tokens.append(int(tok))
        toks.append((rid, len(rec.tokens) - 1))
        if rec.done:
            finished.append(rid)
    win.steps.append(Step(start, end, left, toks))
    return finished


def run_open(eng, sched: Schedule, seconds: float,
             tracer: Tracer) -> Window:
    win = Window(seconds, "open")
    reqs = sched.requests
    i = 0
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while True:
            now = time.perf_counter() - t0
            t0 += tracer.poll(eng, win, now)
            while i < len(reqs) and reqs[i].due <= now:
                _send(eng, win, reqs[i], reqs[i].due, t0)
                i += 1
            if not system.busy(eng):
                if i >= len(reqs):
                    break
                with TraceAnnotation("bench.traffic"):
                    time.sleep(max(0.0, reqs[i].due
                                   - (time.perf_counter() - t0)))
                continue
            if now > seconds + DRAIN_S:
                break
            _step(eng, win, t0)
            if i >= len(reqs) and all(r.times for r in win.records.values()):
                t0 += tracer.poll(eng, win, time.perf_counter() - t0,
                                  last=True)
        t0 += tracer.poll(eng, win, time.perf_counter() - t0, last=True)
    win.end = time.perf_counter() - t0
    win.failed += sum(not r.done for r in win.records.values())
    return win


def run_closed(eng, sched: Schedule, seconds: float,
               tracer: Tracer) -> Window:
    win = Window(seconds, "closed")
    queues = [list(c) for c in sched.clients]
    owner: Dict[int, int] = {}
    t0 = time.perf_counter()

    def send(client: int, now: float) -> None:
        if not queues[client]:
            raise RuntimeError(f"client {client} ran out of requests; "
                               f"raise traffic.CLOSED_LOOP_DEPTH")
        n = len(win.records)
        _send(eng, win, queues[client].pop(0), now, t0)
        for rid in list(win.records)[n:]:
            owner[rid] = client

    with TraceAnnotation("bench.window"):
        for c in range(len(queues)):
            send(c, 0.0)
        while True:
            now = time.perf_counter() - t0
            t0 += tracer.poll(eng, win, now)
            if now >= seconds:
                break
            for rid in _step(eng, win, t0):
                send(owner[rid], time.perf_counter() - t0)
        t0 += tracer.poll(eng, win, time.perf_counter() - t0, last=True)
    win.end = time.perf_counter() - t0
    return win


def warm_up(eng, longest_prompt: int, vocab: int) -> None:
    """Run every program shape the cell's traffic can reach, through the
    engine itself: one request of the longest prompt (every prefill chunk
    offset), then one admission wave of each size from 1 to the slot
    count (the engine's per-wave cache resets and slot updates)."""
    rng = np.random.default_rng(0)

    def serve(lengths, max_new):
        for n in lengths:
            eng.add_request(rng.integers(0, vocab, n).astype(np.int32),
                            max_new_tokens=max_new)
        while system.busy(eng):
            eng.step()

    serve([longest_prompt], 2)
    for n in range(1, eng.scfg.max_slots + 1):
        serve([2] * n, 1)
