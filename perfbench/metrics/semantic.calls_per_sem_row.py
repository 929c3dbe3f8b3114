"""LM calls per row reaching a semantic operator (``llm_calls`` over
``sem_rows``): below 1 by what dedup and the cache saved."""


def read(run):
    """Window's LM calls over its semantic rows; nothing without rows."""
    rows = sum(r["stats"].sem_rows for r in run.records)
    if not rows:
        return None
    return sum(r["stats"].llm_calls for r in run.records) / rows
