"""95th percentile of per-query latency, planning included (host clock)."""
from perfbench.window import percentile


def read(run):
    """Nearest-rank p95 over every query of the window."""
    return percentile([r["latency_s"] for r in run.records], 95)
