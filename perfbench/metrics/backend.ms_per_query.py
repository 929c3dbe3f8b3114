"""Milliseconds per query inside the backend (oracle or LM server): the
benchmark's span around every ``evaluate_batch``, ``submit_batch`` and
``collect``."""


def read(run):
    """Backend span time over the window's queries; nothing if no call."""
    t0 = int(run.t0 * 1e9)
    if not any(n == "bench.backend" and s >= t0
               for n, s, _ in run.spans.items):
        return None
    return 1e3 * run.spans.seconds("bench.backend", t0) / run.n
