"""Device milliseconds per execution of a decode-carrying program."""


def read(run):
    n, dev_s = run.program_time("decode")
    return None if n == 0 else 1e3 * dev_s / n
