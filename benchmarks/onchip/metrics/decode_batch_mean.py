"""Tokens delivered per decode-carrying dispatch (the engine's
``dispatch_counts``: decode + fused) in the window."""


def read(run):
    n = run.counter_delta("dispatch", "decode") \
        + run.counter_delta("dispatch", "fused")
    toks = sum(len(r.tokens) for r in run.win.records.values())
    return None if n == 0 else toks / n
