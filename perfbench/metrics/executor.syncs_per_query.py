"""Data-path device-to-host syncs per query (``ExecStats.pipeline_syncs``)."""


def read(run):
    """Mean pipeline syncs over the window's queries."""
    return sum(r["stats"].pipeline_syncs for r in run.records) / run.n
