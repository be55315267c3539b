"""Reading the program's step records: alignment with the window's steps
from the end, and the cases in which the readers must read nothing."""
from collections import deque
from types import SimpleNamespace

import pytest

import hostspans
from driver import Step, Window


def _record(step, ticks=1, gc_ns=0, gc_gap_ns=0):
    return SimpleNamespace(step=step, ticks=ticks, gc_ns=gc_ns,
                           gc_gap_ns=gc_gap_ns, wall_ns=1000, self_ns={})


def _window(n):
    win = Window(10.0, "closed")
    win.steps = [Step(float(i), i + 0.5, [], []) for i in range(n)]
    return win


def _log(records, maxlen=None):
    return SimpleNamespace(steps=deque(records, maxlen=maxlen))


def test_aligns_from_the_end():
    warm = [_record(i) for i in range(5)]
    window = [_record(5, ticks=4)] + [_record(i) for i in range(9, 12)]
    win = _window(4)
    pairs = hostspans.align(win, _log(warm + window))
    assert [st for st, _ in pairs] == win.steps
    assert [rec for _, rec in pairs] == window


def test_short_ring_reads_nothing():
    log = _log([_record(i) for i in range(20)], maxlen=8)
    assert hostspans.align(_window(9), log) is None
    assert hostspans.align(_window(8), log) is not None


def test_skipped_index_reads_nothing():
    recs = [_record(i) for i in (0, 1, 2, 4, 5)]
    assert hostspans.align(_window(5), _log(recs)) is None
    assert hostspans.align(_window(2), _log(recs)) is not None


def test_no_program_spans_read_nothing(monkeypatch):
    win = _window(3)
    run = SimpleNamespace(win=win, steps=lambda: win.steps)
    monkeypatch.setattr(hostspans, "spans", None)
    assert hostspans.traced_records(run) is None
    assert hostspans.gc_pause_ms(run) is None
    assert hostspans.align(win, None) is None


def test_gc_pause_over_the_traced_steps(monkeypatch):
    win = _window(4)
    recs = [_record(i, gc_ns=10**6 * i, gc_gap_ns=5 * 10**5)
            for i in range(4)]
    run = SimpleNamespace(win=win, steps=lambda: win.steps[2:])
    monkeypatch.setattr(hostspans, "spans",
                        SimpleNamespace(latest=lambda: _log(recs)))
    assert hostspans.traced_records(run) == recs[2:]
    assert hostspans.gc_pause_ms(run) == pytest.approx(2 + 3 + 0.5 + 0.5)


def test_innermost_pieces():
    spans = [("serve.step", 0.0, 10.0), ("serve.decode", 1.0, 3.0),
             ("serve.fetch", 4.0, 8.0), ("serve.step", 12.0, 14.0)]
    assert hostspans.innermost(spans) == [
        ("serve.step", 0.0, 1.0), ("serve.decode", 1.0, 3.0),
        ("serve.step", 3.0, 4.0), ("serve.fetch", 4.0, 8.0),
        ("serve.step", 8.0, 10.0), ("serve.step", 12.0, 14.0)]
