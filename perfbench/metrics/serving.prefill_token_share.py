"""Share of the token positions that the window's prefill launches
computed which held a real prompt token, in %
(``ServingStats.prefill_tokens / prefill_token_slots`` over the window):
the rest is padding, rows and positions up to ``max_seq``."""


def read(run):
    """Real over launched prefill positions; nothing without a prefill."""
    if not run.serving or not run.serving["prefill_token_slots"]:
        return None
    return (100.0 * run.serving["prefill_tokens"]
            / run.serving["prefill_token_slots"])
