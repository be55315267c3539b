"""Prefill programs' share of their roofline: the least time the chip
needs for the valid prompt tokens (operations at peak FLOP/s, or weights
per dispatch plus K/V writes at peak bandwidth, whichever is larger) over
the programs' device time."""


def read(run):
    n, dev_s = run.program_time("prefill")
    tokens, flops = run.prefill_work()
    if n == 0 or tokens == 0:
        return None
    return run.roofline_pct(flops, run.counts.prefill_bytes(n, tokens), dev_s)
