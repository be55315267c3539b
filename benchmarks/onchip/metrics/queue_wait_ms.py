"""p95 of the wait in ``eng.queue``: from when a request was due to the
start of the step that admitted it (host clock)."""
import math

from readers import p95


def read(run):
    v = [r.left_queue - r.due for r in run.requests()
         if not math.isnan(r.left_queue)]
    return None if not v else 1e3 * p95(v)
