"""Cut a recorded chip trace down to a small test fixture.

    python3 benchmarks/onchip/tools/cut_trace.py trace.xplane.pb \\
        OPEN_MS CLOSE_MS out.json.gz

Keeps the TPU planes' "XLA Modules" and "XLA Ops" events and the host's
``bench.*`` spans between OPEN_MS and CLOSE_MS (milliseconds on the trace's
clock), adds the ``bench.trace_open``/``bench.trace_close`` markers there,
and shortens each operation's name to its HLO result (``%name = type``),
which is all the reduction reads. Times are written in seconds.
"""
from __future__ import annotations

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tracing  # noqa: E402


def cut(path: str, lo_ms: float, hi_ms: float) -> dict:
    device, host = tracing.load_events(os.path.dirname(path))
    lo, hi = lo_ms * 1e-3, hi_ms * 1e-3

    def short(name: str) -> str:
        head, _, rest = name.partition(" = ")
        return f"{head} = {rest.split(' ')[0]}" if rest else head

    dev = {plane: [(line, short(name) if line == tracing.OPS_LINE else name,
                    s, e) for line, name, s, e in evs if s >= lo and e <= hi]
           for plane, evs in device.items()}
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in host
             if e > lo and s < hi]
    spans += [(tracing.OPEN, lo, lo), (tracing.CLOSE, hi, hi)]
    return {"device": dev, "host": spans}


if __name__ == "__main__":
    src, lo_ms, hi_ms, out = sys.argv[1:5]
    with gzip.open(out, "wt") as f:
        json.dump(cut(src, float(lo_ms), float(hi_ms)), f)
