"""Seconds from process start to the window: imports, weights, engine,
warm-up of every shape the cell uses, compiles or cache reads."""


def read(run):
    return run.setup_s
