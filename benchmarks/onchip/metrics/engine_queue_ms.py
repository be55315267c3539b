"""p95 of the wait in the engine's own queue, over the requests that
count: from ``add_request`` to the admission wave that took the request
(``Request.t_enqueued`` to ``Request.t_admitted``, set by the program).
The rest of ``queue_wait_ms`` is time the request waited to be sent."""
from readers import p95


def read(run):
    v = [r.handle.t_admitted - r.handle.t_enqueued for r in run.requests()
         if getattr(r.handle, "t_admitted", None) is not None]
    return None if not v else 1e3 * p95(v)
