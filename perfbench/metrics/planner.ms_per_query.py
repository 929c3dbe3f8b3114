"""Milliseconds per query in the PLOP planner: the benchmark's span around
building the plan from its template and ``optimize()``."""


def read(run):
    """Planner span time over the window's queries."""
    return 1e3 * run.spans.seconds("bench.plan", _ns(run.t0)) / run.n


def _ns(t):
    return int(t * 1e9)
