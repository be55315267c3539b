"""Decode programs' share of their roofline: the least time the chip
needs for each step (every weight, the live K/V of each active request
and one new K/V each at peak bandwidth, or the operations at peak FLOP/s)
over the programs' device time."""


def read(run):
    n, dev_s = run.program_time("decode")
    flops, byts, steps = run.decode_work()
    if n == 0 or steps == 0:
        return None
    return run.roofline_pct(flops, byts, dev_s)
