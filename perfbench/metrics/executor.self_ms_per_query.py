"""Milliseconds per query in the executor and the semantic tier's host
path (render, dedup, cache probe, waits on kernels): the query span less
the planner and backend spans inside it."""


def read(run):
    """Query spans less planner and backend spans, per query."""
    t0 = int(run.t0 * 1e9)
    own = (run.spans.seconds("bench.query", t0)
           - run.spans.seconds("bench.plan", t0)
           - run.spans.seconds("bench.backend", t0))
    return 1e3 * own / run.n
