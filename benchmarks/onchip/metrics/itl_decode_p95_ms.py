"""p95 of the decode gaps: the gaps between consecutive tokens of one
request, as ``engine.step()`` hands them to the client, in which no step
admitted a request (host clock)."""
from readers import p95


def read(run):
    gaps, admitting = run.token_gaps()
    gaps = gaps[~admitting]
    return None if not len(gaps) else 1e3 * p95(gaps)
