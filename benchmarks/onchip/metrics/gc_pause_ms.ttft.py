"""Milliseconds Python's collector paused the host over the traced part's
engine steps and the gaps between them (the program's step records)."""
from hostspans import gc_pause_ms as read  # noqa: F401
