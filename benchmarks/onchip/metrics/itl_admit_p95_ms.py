"""p95 of the admission gaps: the gaps between consecutive tokens of one
request, as ``engine.step()`` hands them to the client, in which a step
admitted another request, so that the request waited on its prefill
(host clock)."""
from readers import p95


def read(run):
    gaps, admitting = run.token_gaps()
    gaps = gaps[admitting]
    return None if not len(gaps) else 1e3 * p95(gaps)
