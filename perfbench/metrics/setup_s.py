"""Seconds from process start to the window's start: loading, data and
weights from the seed, compiles and the warm-up cycles (host clock)."""


def read(run):
    """Set-up seconds of this run."""
    return run.setup_s
