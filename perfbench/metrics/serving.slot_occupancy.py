"""Share of decode slot-steps that served a live request, in %
(``ServingStats.live_slot_steps / slot_steps`` over the window)."""


def read(run):
    """Live over all slot-steps of the window; nothing without decoding."""
    if not run.serving or not run.serving["slot_steps"]:
        return None
    return 100.0 * run.serving["live_slot_steps"] / run.serving["slot_steps"]
