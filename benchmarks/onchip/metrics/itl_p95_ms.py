"""p95 over all gaps between consecutive tokens of one request, as
``engine.step()`` hands them to the client (host clock)."""
import numpy as np

from readers import p95


def read(run):
    gaps = []
    for r in run.requests():
        t = run.token_times(r)
        gaps.extend(np.diff(t))
    return None if not gaps else 1e3 * p95(gaps)
