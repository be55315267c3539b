"""Reading a window without the program: the check's sample spreads over
the parts of the batch, and token gaps split into decode and admission
gaps."""
from types import SimpleNamespace

import numpy as np
import pytest

import readers
from driver import Record, Step, Window
from run import sample_finished


def _window(slots_of, lengths=None):
    """A closed-loop window whose finished requests sat in ``slots_of``."""
    win = Window(10.0, "closed")
    for rid, slot in enumerate(slots_of):
        n = (lengths or {}).get(rid, 3)
        win.records[rid] = Record(rid, np.zeros(4, np.int32), 0.0, 0.0,
                                  SimpleNamespace(done=True), slot=slot,
                                  tokens=[1] * n)
    return win


@pytest.mark.parametrize("seed", range(20))
def test_sample_holds_every_part_of_the_batch(seed):
    # 16 slots; one odd slot among many even ones, the longest in slot 0
    slots = [0, 2, 4, 6, 8, 10, 12, 14] * 4 + [9]
    win = _window(slots, lengths={0: 40})
    picked = sample_finished(win, 4, 16, seed)
    assert picked[0].rid == 0
    parts = {(r.slot % 2, r.slot // 8) for r in picked}
    assert parts == {(0, 0), (0, 1), (1, 1)}
    assert len({r.rid for r in picked}) == 4


def test_sample_fills_up_from_any_part_and_from_unknown_slots():
    win = _window([0, 0, 0, -1, -1])
    picked = sample_finished(win, 4, 4, 7)
    assert len({r.rid for r in picked}) == 4
    assert sample_finished(win, 8, 4, 7)[0].rid == 0
    assert len(sample_finished(win, 8, 4, 7)) == 5


def test_token_gaps_split_at_admitting_steps():
    win = Window(10.0, "closed")
    for rid in (0, 1):
        win.records[rid] = Record(rid, np.zeros(2, np.int32), 0.0, 0.0,
                                  SimpleNamespace(done=False))
    # request 0 decodes in steps 0-3; step 2 admits request 1
    ends = [1.0, 2.0, 4.5, 5.0]
    toks = [[(0, 0)], [(0, 1)], [(0, 2), (1, 0)], [(0, 3), (1, 1)]]
    for i, (end, tk) in enumerate(zip(ends, toks)):
        win.steps.append(Step(end - 0.5, end, [1] if i == 2 else [], tk))
        for rid, _ in tk:
            win.records[rid].times.append(end)
            win.records[rid].tokens.append(0)
    run = readers.Run(win, 0.0, None, None, None, {})
    gaps, admitting = run.token_gaps()
    assert gaps.tolist() == [1.0, 2.5, 0.5, 0.5]
    assert admitting.tolist() == [False, True, False, False]
    assert readers.read("itl_admit_p95_ms", run) == pytest.approx(2500.0)
    assert readers.read("itl_decode_p95_ms", run) == pytest.approx(
        1e3 * np.percentile([1.0, 0.5, 0.5], 95))
