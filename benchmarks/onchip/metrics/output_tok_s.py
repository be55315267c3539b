"""Output tokens delivered in the window, over the window's seconds."""


def read(run):
    n = sum(len(run.token_times(r)) for r in run.requests())
    return n / run.seconds
