"""Wall-clock spans and counters inside the serving engine.

One mechanism: ``span(log, name, **ids)`` opens a
``jax.profiler.TraceAnnotation(name, **ids)``, so the span lands in the
profiler's own trace on the device's clock, and adds its
``perf_counter_ns`` duration to the engine's step record in ``log``. One
call gives both, so the two can never name a phase differently. With no
profiler running a span costs about a microsecond, so the spans are always
on.

Spans of ``ServeEngine`` (every name starts with ``serve.``):

  serve.step     one ``ServeEngine.step()``; argument ``step=<step_idx>``
  serve.admit    ``admit_wave``: queue sort, slot pick, cache-row reset,
                 slot length and budget updates
  serve.prefill  one prefill dispatch, chunked or packed
  serve.arm      ``finish_prefill``: a wave's slots armed for decode
  serve.decode   one decode, superstep or fused dispatch call
  serve.fetch    the blocking fetch of a decode's (token, done, len)
  serve.apply    the rest of a resolve: token appends, completions, hooks

Each ``serve.step`` leaves one ``StepRecord`` in ``StepLog.steps``, a ring
of the newest ``RING_STEPS`` steps: the wall time of the step, the self
time of each span inside it, the garbage collector's pauses inside the step
and in the gap since the previous one, and the backend compiles that
landed inside it. The pauses come from one ``gc.callbacks`` hook and the
compiles from one ``jax.monitoring`` listener, both installed once per
process.

``latest()`` is the scrape point: the newest engine's log stays reachable
after the engine is freed; older logs are held weakly. Nothing here reads
a device array, so the spans add no dispatch and no host sync.
"""
from __future__ import annotations

import collections
import gc
import weakref
from dataclasses import dataclass
from time import perf_counter_ns
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

STEP = "serve.step"
RING_STEPS = 16384
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# process-wide clocks: GC pause ns so far, backend compiles and seconds
_gc = {"ns": 0, "start": 0}
_compiles = {"n": 0, "s": 0.0}
_installed = False
_latest: Optional["StepLog"] = None
_logs: "weakref.WeakSet[StepLog]" = weakref.WeakSet()


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc["start"] = perf_counter_ns()
    else:
        _gc["ns"] += perf_counter_ns() - _gc["start"]


def _on_event_duration(event: str, seconds: float, **kwargs) -> None:
    if event == BACKEND_COMPILE:
        _compiles["n"] += 1
        _compiles["s"] += seconds


def _install() -> None:
    global _installed
    if not _installed:
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _installed = True


@dataclass(slots=True)
class StepRecord:
    step: int                  # engine clock at the step's start
    ticks: int                 # clock ticks it advanced (k for a superstep)
    kind: Optional[str]        # the scheduler's step kind (Scheduler._tick)
    wall_ns: int               # serve.step
    self_ns: Dict[str, int]    # self time of each span that ran inside it
    gc_ns: int                 # GC pauses inside the step
    gc_gap_ns: int             # GC pauses since the previous step ended
    compiles: int              # backend compiles inside the step
    compile_s: float           # and their seconds


class StepLog:
    """One engine's step records and the state of its open step."""

    def __init__(self, maxlen: int = RING_STEPS):
        global _latest
        _install()
        self.steps: "collections.deque[StepRecord]" = \
            collections.deque(maxlen=maxlen)
        self.n_steps = 0             # records ever written
        self._stack: List[span] = []  # spans open inside the current step
        self._self: Dict[str, int] = {}
        # GC ns, compiles and compile seconds at the open step's start / end
        self._marks = self._ends = (0, 0, 0.0)
        self._wall = 0
        self._gc_end = _gc["ns"]     # GC ns when the previous step ended
        _latest = self
        _logs.add(self)

    def _begin(self) -> None:
        self._self = {}
        self._marks = (_gc["ns"], _compiles["n"], _compiles["s"])

    def _end(self, wall_ns: int) -> None:
        self._wall = wall_ns
        self._ends = (_gc["ns"], _compiles["n"], _compiles["s"])

    def record(self, step: int, ticks: int, kind: Optional[str]) -> None:
        """Close the step whose ``serve.step`` span just ended."""
        gc0, n0, s0 = self._marks
        gc1, n1, s1 = self._ends
        self.steps.append(StepRecord(
            step=step, ticks=ticks, kind=kind, wall_ns=self._wall,
            self_ns=self._self, gc_ns=gc1 - gc0, gc_gap_ns=gc0 - self._gc_end,
            compiles=n1 - n0, compile_s=s1 - s0))
        self._gc_end = gc1
        self.n_steps += 1


class span:
    """``with span(log, name, **ids):`` one profiler annotation and, inside
    an open ``serve.step``, one self time in that step's record. A span
    outside any step only annotates."""

    __slots__ = ("_log", "_name", "_ann", "_t0", "_child", "_on")

    def __init__(self, log: StepLog, name: str, **ids):
        self._log, self._name = log, name
        self._ann = TraceAnnotation(name, **ids)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        stack = self._log._stack
        self._on = bool(stack) or self._name == STEP
        if self._on:
            if not stack:
                self._log._begin()
            stack.append(self)
            self._child = 0
            self._t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._on:
            dur = perf_counter_ns() - self._t0
            log = self._log
            log._stack.pop()
            if log._stack:
                log._stack[-1]._child += dur
                log._self[self._name] = (log._self.get(self._name, 0)
                                         + dur - self._child)
            else:
                log._end(dur)
        self._ann.__exit__(*exc)


def latest() -> Optional[StepLog]:
    """The newest engine's step log, alive after its engine is freed."""
    return _latest


def logs() -> List[StepLog]:
    """Every step log whose engine (or caller) still holds it."""
    return list(_logs)
