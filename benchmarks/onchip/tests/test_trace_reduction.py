"""The trace reduction on a short traced chip run, cut down.

``data/olmo-1b_trace.json.gz``: olmo-1b at 16 slots x 2048 on one v5e, one
admission wave of 16 x 700-token prompts (three 256-token prefill chunks,
the wave's cache resets) and then decode steps, cut to 574 ms by
``tools/cut_trace.py``. Busy time, the idle share and the attribution of
idle time to host spans are checked against a brute-force sweep over a
fine time grid, which shares no code with the reduction.
"""
import gzip
import json
import os

import numpy as np
import pytest

import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "olmo-1b_trace.json.gz")
GRID = 1e-6          # seconds per grid cell of the brute-force check


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        d = json.load(f)
    device = {k: [tuple(e) for e in v] for k, v in d["device"].items()}
    return device, [tuple(h) for h in d["host"]]


@pytest.fixture(scope="module")
def reduced(events):
    return tracing.reduce_events(*events, slots=16)


def _grid(events):
    device, host = events
    marks = {n: s for n, s, _ in host}
    lo, hi = marks[tracing.OPEN], marks[tracing.CLOSE]
    n = int(round((hi - lo) / GRID))
    t = lo + (np.arange(n) + 0.5) * GRID
    busy = np.zeros(n, bool)
    for line, _, s, e in next(iter(device.values())):
        if line == tracing.OPS_LINE:
            busy |= (t >= s) & (t < e)
    spans = {}
    for name, s, e in host:
        if name.startswith("bench.") and name not in (
                tracing.OPEN, tracing.CLOSE, tracing.WINDOW_SPAN):
            spans.setdefault(name, np.zeros(n, bool))
            spans[name] |= (t >= s) & (t < e)
    return lo, hi, busy, spans


def test_busy_union_matches_a_grid(events, reduced):
    lo, hi, busy, _ = _grid(events)
    assert reduced["window_s"] == pytest.approx(hi - lo)
    assert reduced["busy_s"] == pytest.approx(busy.sum() * GRID, abs=2e-5)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_idle_share_and_attribution_match_a_grid(events, reduced):
    lo, hi, busy, spans = _grid(events)
    idle = ~busy
    for name, mask in spans.items():
        want = (idle & mask).sum() * GRID
        assert reduced["idle_by_span"].get(name, 0.0) == pytest.approx(
            want, abs=2e-5), name
    total = sum(reduced["idle_by_span"].values())
    assert total == pytest.approx(idle.sum() * GRID, abs=2e-5)
    # no bench.traffic span here: the engine held work all through
    assert reduced["idle_work_s"] == pytest.approx(total, abs=1e-9)


def test_program_roles(reduced):
    roles = {}
    for p in reduced["programs"].values():
        roles.setdefault(p["role"], []).append(p)
    assert sorted(p["n"] for p in roles["prefill"]) == [1, 1, 1]
    assert [p["n"] for p in roles["decode"]] == [4]
    assert all(0.1 < p["device_s"] < 0.13 for p in roles["prefill"])
    assert 0.02 < roles["decode"][0]["device_s"] / 4 < 0.025


def test_breakdown_is_bounded_and_sorted(reduced):
    b = tracing.breakdown(reduced, top=10)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0].startswith("prefill:")


def test_interval_helpers():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tracing.complement([(1, 2), (3, 4)], 0, 5) == [
        (0, 1), (2, 3), (4, 5)]
    assert tracing.overlap([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2)
