"""Distinct prompts sent to the backend per query (``ExecStats.llm_calls``):
the paper's LLM cost, which placement trades against latency."""


def read(run):
    """Mean LM calls over the window's queries."""
    return sum(r["stats"].llm_calls for r in run.records) / run.n
