"""Queries completed over the window's seconds (host clock)."""
from perfbench.window import rate


def read(run):
    """All the window's queries over all its time."""
    return rate(run.n, run.t0, run.t1)
