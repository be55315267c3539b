"""Median host milliseconds of a plain decode step in the traced part:
``serve.step`` minus its blocking ``serve.fetch``, over the steps that
carried a decode and admitted nothing (the program's step records)."""
import numpy as np

import hostspans


def read(run):
    recs = hostspans.traced_records(run)
    if recs is None:
        return None
    v = [r.wall_ns - r.self_ns.get("serve.fetch", 0) for r in recs
         if "serve.decode" in r.self_ns and "serve.admit" not in r.self_ns]
    return None if not v else hostspans.MS * float(np.median(v))
