"""The generator gives every seed the same work in the same order, with
the seed's own token ids, and keeps open-loop arrivals inside the
window."""
import numpy as np
import pytest

import cells
import traffic

SEEDS = (3, 2**31 + 5)


def _reqs(sched):
    return sched.requests or [r for c in sched.clients for r in c]


@pytest.mark.parametrize("cell", cells.names())
def test_seeds_share_the_work_and_differ_in_tokens(cell):
    m = cells.load(cell).mix
    a, b = (_reqs(traffic.build(m, s, 51, 1000)) for s in SEEDS)
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    if m["loop"] == "open":
        due = [r.due for r in a]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 51


def test_lognormal_keeps_its_mean_and_clip():
    spec = {"dist": "lognormal", "mean": 300.0, "sigma": 0.5,
            "clip": [1, 10**6]}
    u = (np.arange(4000) + 0.5) / 4000
    v = traffic.quantile_lengths(spec, u)
    assert abs(v.mean() - 300.0) < 3.0
    clipped = traffic.quantile_lengths(dict(spec, clip=[100, 400]), u)
    assert clipped.min() == 100 and clipped.max() == 400
