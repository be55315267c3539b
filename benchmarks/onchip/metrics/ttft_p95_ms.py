"""p95 of time to first token over every request due in the window, timed
from when it was due (host clock)."""
from readers import p95


def read(run):
    v = [r.times[0] - r.due for r in run.requests() if r.times]
    return None if not v else 1e3 * p95(v)
